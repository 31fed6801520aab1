"""Plain float32 reference of the dense decoder family.

RMSNorm, rotary embeddings (rotate-half, theta^(-2i/d)), grouped-query
causal attention with optional per-head query/key RMSNorm, a SwiGLU MLP,
and a tied or untied output head, in straightforward ``jax.numpy`` at
``Precision.HIGHEST``, one layer at a time, with the weights drawn again
from the seed.  It follows the published Llama/Qwen3 description and
imports nothing of the program under test.

``mode="fp8"`` is the control: every matrix product takes float8 (e4m3)
operands, the weights scaled per tensor and the activations per row.

Another family's reference may build on this one: ``attention``,
``swiglu`` and ``sizes`` are its parts, and ``logits_at`` runs any layer
function over the layers the weights module draws.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import weights

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _q8(a, axis):
    """Round ``a`` to float8 e4m3 after scaling its absolute maximum (over
    ``axis``; None = the whole tensor) to the format's largest value."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=axis is not None)
    scale = FP8_MAX / jnp.maximum(amax, 1e-30)
    return (a * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(x, w, mode):
    if mode == "fp8":
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.einsum("...d,de->...e", x, w, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv          # [T, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, p, d, mode):
    """``x`` plus its causal grouped-query attention sublayer, ``x`` [N, T,
    D] (causal within each row); leaves under ``attn.`` and
    ``attn_norm``."""
    H, KV, hd, eps, theta = d["H"], d["KV"], d["hd"], d["eps"], d["theta"]
    N, T, _ = x.shape
    pos = jnp.arange(T)
    h = _rmsnorm(x, p["attn_norm"], eps)
    q = _mm(h, p["attn.wq"], mode).reshape(N, T, H, hd)
    k = _mm(h, p["attn.wk"], mode).reshape(N, T, KV, hd)
    v = _mm(h, p["attn.wv"], mode).reshape(N, T, KV, hd)
    if d["qk_norm"]:
        q = _rmsnorm(q, p["attn.q_norm"], eps)
        k = _rmsnorm(k, p["attn.k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    if mode == "fp8":
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    mask = pos[None, :] <= pos[:, None]                   # [Tq, Tk]

    def attend(qkv):                                      # one sequence
        qs, ks, vs = qkv
        qg = qs.reshape(T, KV, H // KV, hd)
        s = jnp.einsum("tkgd,skd->kgts", qg, ks,
                       precision=HIGHEST) / np.sqrt(hd)
        s = jnp.where(mask, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgts,skd->tkgd", a, vs, precision=HIGHEST)
        return o.reshape(T, H * hd)

    o = jax.lax.map(attend, (q, k, v))
    return x + _mm(o, p["attn.wo"], mode)


def swiglu(h, w_gate, w_up, w_down, mode):
    """The SwiGLU feed-forward of ``h``."""
    g = _mm(h, w_gate, mode)
    u = _mm(h, w_up, mode)
    return _mm(jax.nn.silu(g) * u, w_down, mode)


@functools.partial(jax.jit, static_argnames=("d", "mode"))
def _layer(x, p, *, d, mode):
    """One decoder layer over ``x`` [N, T, D] (causal within each row)."""
    x = attention(x, p, d, mode)
    h = _rmsnorm(x, p["mlp_norm"], d["eps"])
    return x + swiglu(h, p["mlp.w_gate"], p["mlp.w_up"], p["mlp.w_down"],
                      mode)


@functools.partial(jax.jit, static_argnames=("tied", "eps", "mode"))
def _head(h, top, *, tied, eps, mode):
    h = _rmsnorm(h, top["final_norm"], eps)
    w = top["embed"].T if tied else top["lm_head"]
    return _mm(h, w, mode)


def sizes(c: dict, **extra) -> dict:
    """The static sizes a layer function reads, hashable, with ``extra``."""
    dd = weights.dims(c)
    return _Frozen({"H": dd["H"], "KV": dd["KV"], "hd": dd["hd"],
                    "eps": float(c["rms_norm_eps"]),
                    "theta": float(c["rope_theta"]),
                    "qk_norm": dd["qk_norm"], **extra})


def logits_at(c: dict, seed: int, fn: int, seqs: list, positions: list,
              mode: str = "f32", pad_to: int = 512, layer=None,
              d: dict | None = None) -> list:
    """Logits of function ``fn`` at ``positions[i]`` of sequence ``seqs[i]``.

    One sequence at a time, padded to a multiple of ``pad_to`` (causal
    attention keeps the padding out of every real position), so the few
    padded lengths compile once.  Each layer's weights are drawn once for
    all sequences and run through ``layer(x, p, d=d, mode=mode)`` (this
    family's ``_layer`` and ``sizes(c)`` unless given: another family's
    reference passes its own).  Returns one float32 ``[len(positions[i]),
    vocab]`` device array per sequence."""
    layer = layer or _layer
    d = d or sizes(c)
    top = weights.top_params(c, seed, fn)
    xs = []
    for s in seqs:
        toks = np.zeros((1, pad_to * -(-len(s) // pad_to)), np.int32)
        toks[0, :len(s)] = s
        xs.append(jnp.take(top["embed"], jnp.asarray(toks), axis=0))
    for i in range(c["num_hidden_layers"]):
        p = weights.layer_params(c, seed, fn, i)
        xs = [layer(x, p, d=d, mode=mode) for x in xs]
        del p
    out = []
    for x, pp in zip(xs, positions):
        pp = np.asarray(pp)
        rows = np.zeros(pad_to * -(-len(pp) // pad_to), np.int32)
        rows[:len(pp)] = pp
        h = x[0, jnp.asarray(rows)]
        z = _head(h, top, tied=bool(c["tie_word_embeddings"]),
                  eps=d["eps"], mode=mode)
        out.append(z[:len(pp)])
    return out


class _Frozen(dict):
    """A hashable dict, so the layer's sizes can be a static argument."""

    def __init__(self, items):
        super().__init__(items)
        self._key = tuple(sorted(self.items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key == other._key
