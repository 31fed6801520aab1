"""Seeded random weights for the dense family, made on the device.

The served model gets them in its parameter layout and serving dtype from
one jitted call (``served_params``); the reference draws the very same
values again, one layer at a time, from the same seed (``layer_params``,
``top_params``), so it needs nothing the program made.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def dims(c: dict) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    return {"D": D, "H": H, "KV": c["num_key_value_heads"],
            "hd": c.get("head_dim") or D // H, "F": c["intermediate_size"],
            "V": c["vocab_size"], "L": c["num_hidden_layers"],
            "tied": bool(c["tie_word_embeddings"]),
            "qk_norm": bool(c.get("qk_norm", False))}


def layer_leaves(c: dict) -> dict:
    """One decoder layer's leaves: path -> (shape, kind)."""
    d = dims(c)
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    out = {"attn_norm": ((D,), "norm"), "mlp_norm": ((D,), "norm"),
           "attn.wq": ((D, H * hd), "mat"), "attn.wk": ((D, KV * hd), "mat"),
           "attn.wv": ((D, KV * hd), "mat"), "attn.wo": ((H * hd, D), "mat"),
           "mlp.w_gate": ((D, F), "mat"), "mlp.w_up": ((D, F), "mat"),
           "mlp.w_down": ((F, D), "mat")}
    if d["qk_norm"]:
        out["attn.q_norm"] = ((hd,), "norm")
        out["attn.k_norm"] = ((hd,), "norm")
    return out


def top_leaves(c: dict) -> dict:
    """Leaves outside the layers: path -> (shape, kind)."""
    d = dims(c)
    out = {"embed": ((d["V"], d["D"]), "embed"),
           "final_norm": ((d["D"],), "norm")}
    if not d["tied"]:
        out["lm_head"] = ((d["D"], d["V"]), "mat")
    return out


def n_params(c: dict) -> dict:
    """Parameter counts: per layer, outside the layers, and in all."""
    per_layer = sum(int(np.prod(s)) for s, _ in layer_leaves(c).values())
    top = sum(int(np.prod(s)) for s, _ in top_leaves(c).values())
    return {"per_layer": per_layer, "top": top,
            "total": per_layer * dims(c)["L"] + top}


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
        return z / np.sqrt(shape[1])
    return z / np.sqrt(shape[0])  # fan-in scaled


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split(".")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out


def served_params(c: dict, seed: int, fn: int, dtype=jnp.bfloat16):
    """The whole parameter tree of function ``fn`` in the served layout
    (layers stacked on a leading axis), drawn on the device in one call."""
    layer = tuple(sorted(layer_leaves(c).items()))
    top = tuple(sorted(top_leaves(c).items()))
    key = (layer, top, dims(c)["L"], dtype)
    if key not in _SERVED_FNS:
        L = dims(c)["L"]

        def gen(k0):
            out = {p: _draw(_leaf_key(k0, p), s, k).astype(dtype)
                   for p, (s, k) in top}
            for p, (s, k) in layer:
                lk = _leaf_key(k0, "blocks." + p)
                out["blocks." + p] = jax.vmap(
                    lambda i, lk=lk, s=s, k=k: _draw(
                        jax.random.fold_in(lk, i), s, k).astype(dtype)
                )(jnp.arange(L))
            return _nest(out)
        _SERVED_FNS[key] = jax.jit(gen)
    return _SERVED_FNS[key](base_key(seed, fn))


def layer_params(c: dict, seed: int, fn: int, layer: int,
                 dtype=jnp.bfloat16) -> dict:
    """Layer ``layer`` of function ``fn`` as float32 arrays holding the
    served ``dtype`` values (flat paths, no layer axis)."""
    return _layer_fn(tuple(sorted(layer_leaves(c).items())), dtype)(
        base_key(seed, fn), jnp.int32(layer))


def top_params(c: dict, seed: int, fn: int, dtype=jnp.bfloat16) -> dict:
    """The leaves outside the layers, as float32 holding ``dtype`` values."""
    spec = tuple(sorted(top_leaves(c).items()))
    if (spec, dtype) not in _TOP_FNS:
        _TOP_FNS[(spec, dtype)] = jax.jit(lambda key: {
            p: _draw(_leaf_key(key, p), s, k).astype(dtype)
            .astype(jnp.float32) for p, (s, k) in spec})
    return _TOP_FNS[(spec, dtype)](base_key(seed, fn))


_SERVED_FNS: dict = {}
_LAYER_FNS: dict = {}
_TOP_FNS: dict = {}


def _layer_fn(spec: tuple, dtype):
    if (spec, dtype) not in _LAYER_FNS:
        def gen(key, i):
            return {p: _draw(jax.random.fold_in(
                _leaf_key(key, "blocks." + p), i), s, k)
                .astype(dtype).astype(jnp.float32) for p, (s, k) in spec}
        _LAYER_FNS[(spec, dtype)] = jax.jit(gen)
    return _LAYER_FNS[(spec, dtype)]
