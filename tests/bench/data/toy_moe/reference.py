"""Plain float32 reference of the toy mixture-of-experts family: the dense
reference's attention, then a softmax router whose top
``num_experts_per_tok`` weights, renormalised, mix those experts' SwiGLU
outputs, plus the shared experts' SwiGLU.  Every expert runs on every
token and is weighted by its gate, zero where the router did not pick it.
"""

import functools

import jax
import jax.numpy as jnp

from benchlib import spec, weights

dense = spec.reference("references/dense.py")


@functools.partial(jax.jit, static_argnames=("d", "mode"))
def _layer(x, p, *, d, mode):
    x = dense.attention(x, p, d, mode)
    h = dense._rmsnorm(x, p["mlp_norm"], d["eps"])
    probs = jax.nn.softmax(dense._mm(h, p["moe.router"], mode), axis=-1)
    top, idx = jax.lax.top_k(probs, d["K"])
    top = top / top.sum(-1, keepdims=True)
    out = dense.swiglu(h, p["moe.shared.w_gate"], p["moe.shared.w_up"],
                       p["moe.shared.w_down"], mode)
    for e in range(d["E"]):
        gate = jnp.sum(jnp.where(idx == e, top, 0.0), -1, keepdims=True)
        out = out + gate * dense.swiglu(
            h, p["moe.experts.w_gate"][e], p["moe.experts.w_up"][e],
            p["moe.experts.w_down"][e], mode)
    return x + out


def logits_at(c: dict, seed: int, fn: int, seqs: list, positions: list,
              mode: str = "f32", pad_to: int = 512) -> list:
    dd = weights.dims(c)
    return dense.logits_at(c, seed, fn, seqs, positions, mode, pad_to,
                           layer=_layer, d=dense.sizes(c, E=dd["E"],
                                                       K=dd["K"]))
