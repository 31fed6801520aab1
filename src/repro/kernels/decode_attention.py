"""Flash-decoding over a dense per-slot KV cache as a Pallas TPU kernel.

TPU adaptation notes:
  * decode attention is MEMORY-bound (one query row vs a 32k..500k cache);
    the kernel streams KV blocks HBM->VMEM once and keeps the online softmax
    state for the whole query-group tile in VMEM scratch;
  * the grid is (batch, kv_head, kv_blocks), kv innermost (sequential) —
    all G=H/KV query heads of one KV head form the [G, d] tile processed
    together, so GQA costs one KV pass regardless of G (the MXU contraction
    is [G x d] @ [d x bk]);
  * variable cache occupancy is handled with per-batch lengths that ride
    in as a SCALAR-PREFETCH argument (SMEM) masking the tail block — the
    serve path grows the cache position per step without re-tiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, scale: float, bk: int, nk: int):
    """Grid point (b, h, t): one [bk, d] KV block of batch b, KV head h.

    ``len_ref`` is the [B] lengths vector in SMEM.  Scratch: ``acc_ref``
    [G, d] fp32 accumulator, ``m_ref``/``l_ref`` [G, 1] running max /
    normalizer — persistent across the innermost (sequential) KV-block
    axis, initialized at t == 0, emitted at t == nk-1.
    """
    b = pl.program_id(0)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    # skip blocks entirely past the valid cache region
    @pl.when(t * bk < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [G, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)            # [bk, d]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, bk]
        cols = t * bk + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        scores = jnp.where(cols < length, scores, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(t == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(q, k, v, length, *, block_k: int = 256,
                     interpret: bool | None = None):
    """Single-token attention against a dense [B, KV, T, d] cache.

    Args:
      q: [B, H, d] query block (one decode token per sequence).
      k, v: [B, KV, T, d] head-major KV cache.
      length: scalar or [B] valid cache positions per sequence.
      block_k: KV tile size (clamped to T; must divide it).
      interpret: force Pallas interpret mode (defaults to CPU backend).

    Returns:
      [B, H, d] attention output in ``q.dtype``.
    """
    B, H, d = q.shape
    KV, T = k.shape[1], k.shape[2]
    assert H % KV == 0
    G = H // KV
    bk = min(block_k, T)
    assert T % bk == 0
    nk = T // bk
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    scale = 1.0 / np.sqrt(d)

    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))
    qg = q.reshape(B, KV, G, d)

    kernel = functools.partial(_kernel, scale=scale, bk=bk, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                  # per-batch lengths
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, d), lambda b, h, t, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, t, ln: (b, h, t, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, t, ln: (b, h, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, d),
                               lambda b, h, t, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, d), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, d), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(lengths, qg, k, v)
    return out.reshape(B, H, d)
