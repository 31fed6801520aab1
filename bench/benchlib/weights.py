"""Seeded random weights for any model family, made on the device.

The family's plug-in (``bench/families/<family>.py``) gives the layout:
stacked groups of layers and the leaves outside them.  The served model
gets the weights in that layout and its serving dtype from one jitted call
(``served_params``); the reference draws the very same values again, one
layer at a time, from the same seed (``layer_params``, ``top_params``), so
it needs nothing the program made.  Leaf ``p`` of group ``g`` for global
layer ``i`` is drawn from ``fold_in(_leaf_key(k0, g + "." + p), i)``, a
top-level leaf ``p`` from ``_leaf_key(k0, p)``.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import spec


def family(c: dict):
    """The plug-in of the configuration's model family."""
    return spec.family(c["family"])


def dims(c: dict) -> dict:
    return family(c).dims(c)


def n_params(c: dict) -> dict:
    """Parameter counts: ``total``, and ``active`` per decoded token."""
    return family(c).n_params(c)


def served_dtype(c: dict):
    return jnp.dtype(c["dtype"])


def _groups(c: dict) -> tuple:
    return tuple((g, tuple(layers), tuple(sorted(
        (p, (tuple(s), k)) for p, (s, k) in leaves.items())))
        for g, layers, leaves in family(c).groups(c))


def _top(c: dict) -> tuple:
    return tuple(sorted((p, (tuple(s), k))
                        for p, (s, k) in family(c).top_leaves(c).items()))


def base_key(seed: int, fn: int):
    """The PRNG key of function ``fn``'s weights under ``seed``."""
    words = np.random.SeedSequence([int(seed), 7, int(fn)]).generate_state(2)
    key = jax.random.PRNGKey(int(words[0] >> 1))
    return jax.random.fold_in(key, int(words[1] >> 1))


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw(key, shape, kind: str):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        return 1.0 + 0.1 * z
    if kind == "embed":          # rows of RMS 1/sqrt(D): tied logits ~ 1
        return z / np.sqrt(shape[-1])
    return z / np.sqrt(shape[-2])  # fan-in scaled; a leading axis stacks


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split(".")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out


def served_params(c: dict, seed: int, fn: int):
    """The whole parameter tree of function ``fn`` in the served layout
    (each group's layers stacked on a leading axis) and dtype, drawn on
    the device in one call."""
    groups, top, dtype = _groups(c), _top(c), served_dtype(c)
    key = (groups, top, dtype)
    if key not in _SERVED_FNS:
        def gen(k0):
            out = {p: _draw(_leaf_key(k0, p), s, k).astype(dtype)
                   for p, (s, k) in top}
            for g, layers, leaves in groups:
                idx = jnp.asarray(layers, jnp.int32)
                for p, (s, k) in leaves:
                    lk = _leaf_key(k0, g + "." + p)
                    out[g + "." + p] = jax.vmap(
                        lambda i, lk=lk, s=s, k=k: _draw(
                            jax.random.fold_in(lk, i), s, k).astype(dtype)
                    )(idx)
            return _nest(out)
        _SERVED_FNS[key] = jax.jit(gen)
    return _SERVED_FNS[key](base_key(seed, fn))


def layer_params(c: dict, seed: int, fn: int, layer: int) -> dict:
    """Global layer ``layer`` of function ``fn``, from whichever group
    holds it, as float32 arrays holding the served dtype's values (flat
    paths without the group's prefix, no layer axis)."""
    for g, layers, leaves in _groups(c):
        if layer in layers:
            return _layer_fn(g, leaves, served_dtype(c))(
                base_key(seed, fn), jnp.int32(layer))
    raise KeyError(f"no layer {layer} in {c['name']}")


def top_params(c: dict, seed: int, fn: int) -> dict:
    """The leaves outside the layers, as float32 holding the served
    dtype's values."""
    top, dtype = _top(c), served_dtype(c)
    if (top, dtype) not in _TOP_FNS:
        _TOP_FNS[(top, dtype)] = jax.jit(lambda key: {
            p: _draw(_leaf_key(key, p), s, k).astype(dtype)
            .astype(jnp.float32) for p, (s, k) in top})
    return _TOP_FNS[(top, dtype)](base_key(seed, fn))


_SERVED_FNS: dict = {}
_LAYER_FNS: dict = {}
_TOP_FNS: dict = {}


def _layer_fn(group: str, leaves: tuple, dtype):
    if (group, leaves, dtype) not in _LAYER_FNS:
        def gen(key, i):
            return {p: _draw(jax.random.fold_in(
                _leaf_key(key, group + "." + p), i), s, k)
                .astype(dtype).astype(jnp.float32) for p, (s, k) in leaves}
        _LAYER_FNS[(group, leaves, dtype)] = jax.jit(gen)
    return _LAYER_FNS[(group, leaves, dtype)]
