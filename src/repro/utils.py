"""Small shared utilities: pytree paths, byte accounting, dtype helpers,
and the persistent compile cache of the entry points."""

from __future__ import annotations

import os
import pathlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


# the checkout: src/repro/utils.py -> repo root
_REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points call this before their first compile.  A set
    ``$JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it itself); otherwise
    the cache sits at ``.jax_cache`` in the checkout — a fixed path, so a
    later run of the same checkout finds what earlier runs compiled.
    Every executable is cached however fast it compiled: the per-shape
    prefill and decode programs are what a second run saves.  On the CPU
    backend nothing is enabled (returns None): its cached executables
    are tied to the host's CPU features, and CPU rehearsals compile fast.
    """
    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def path_str(path) -> str:
    """Render a jax.tree_util key path as 'a.b.0.c'."""
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return ".".join(parts)


def tree_paths_and_leaves(tree) -> list[tuple[str, Any]]:
    return [(path_str(p), leaf) for p, leaf in jax.tree_util.tree_leaves_with_path(tree)]


def leaf_bytes(leaf) -> int:
    """Bytes of a leaf (works for jnp arrays, numpy arrays and ShapeDtypeStruct)."""
    return int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize if leaf.shape else np.dtype(leaf.dtype).itemsize


def tree_bytes(tree) -> int:
    return sum(leaf_bytes(l) for l in jax.tree_util.tree_leaves(tree))


def tree_param_count(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))


def fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024:
            return f"{n:.2f} {unit}"
        n /= 1024
    return f"{n:.2f} PiB"


def fmt_time(s: float) -> str:
    if s < 1e-3:
        return f"{s * 1e6:.1f} us"
    if s < 1.0:
        return f"{s * 1e3:.1f} ms"
    return f"{s:.3f} s"


def map_with_path(fn: Callable[[str, Any], Any], tree):
    """tree_map with the flattened string path as first arg."""
    return jax.tree_util.tree_map_with_path(lambda p, l: fn(path_str(p), l), tree)


def assert_no_nans(tree, where: str = "") -> None:
    for path, leaf in tree_paths_and_leaves(tree):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            if bool(jnp.any(jnp.isnan(leaf))):
                raise AssertionError(f"NaN in {where}:{path}")


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return ceil_div(a, b) * b
