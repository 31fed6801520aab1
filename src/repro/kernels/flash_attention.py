"""Flash attention (prefill) as a Pallas TPU kernel.

Design for TPU (not a CUDA port):
  * grid = (batch, q_head, q_blocks, kv_blocks) with the KV axis innermost —
    on TPU the last grid axis iterates sequentially on-core, so the online
    softmax state (m, l, acc) lives in VMEM scratch and carries across KV
    steps without HBM traffic;
  * BlockSpecs tile Q/K/V into VMEM: [bq, d] query tiles against [bk, d]
    KV tiles, d kept whole (head_dim <= 256 fits VMEM comfortably; MXU
    sees [bq x d] @ [d x bk] contractions, both 128-aligned by default);
  * GQA is handled in the index map: the KV block index is q_head // group,
    so no KV duplication in HBM or VMEM;
  * causal masking skips fully-masked KV blocks via pl.when (structural
    skip, halves prefill work) and masks the diagonal block elementwise;
  * fp32 accumulation throughout, output cast to the input dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = float(np.finfo(np.float32).min)


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, scale: float, causal: bool, softcap: float,
            bq: int, bk: int, nk: int):
    """Grid point (b, h, s, t): Q tile s against KV tile t of head h.

    Scratch: ``acc_ref`` [bq, d] fp32 accumulator, ``m_ref``/``l_ref``
    [bq, 1] running max / normalizer — persistent across the innermost
    (sequential) KV axis, initialized at t == 0, emitted at t == nk-1.
    """
    t = pl.program_id(3)
    s = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # structural skip: block fully above the diagonal contributes nothing
    diag_ok = (t * bk <= (s + 1) * bq - 1) if causal else True

    @pl.when(diag_ok)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)            # [bk, d]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if softcap > 0:
            scores = jnp.tanh(scores / softcap) * softcap
        if causal:
            rows = s * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = t * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            scores = jnp.where(cols <= rows, scores, NEG_INF)

        m_prev = m_ref[...]                            # [bq, 1]
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)                    # [bq, bk]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(t == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, softcap: float = 0.0,
                    block_q: int = DEFAULT_BQ, block_k: int = DEFAULT_BK,
                    interpret: bool | None = None):
    """Multi-token (prefill) attention, causal by default.

    Args:
      q: [B, H, S, d] queries.
      k, v: [B, KV, T, d] keys/values (GQA: H a multiple of KV).
      causal: apply the causal mask (requires S == T).
      softcap: logit soft-capping (0 disables).
      block_q, block_k: Q/KV tile sizes (clamped; must divide S/T).
      interpret: force Pallas interpret mode (defaults to CPU backend).

    Returns:
      [B, H, S, d] attention output in ``q.dtype``.
    """
    B, H, S, d = q.shape
    KV, T = k.shape[1], k.shape[2]
    assert H % KV == 0
    if causal:
        assert S == T, "causal path assumes aligned Q/KV (prefill)"
    G = H // KV
    bq = min(block_q, S)
    bk = min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    ns, nk = S // bq, T // bk
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    scale = 1.0 / np.sqrt(d)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               softcap=softcap, bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, ns, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, s, t: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, s, t: (b, h // G, t, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, s, t: (b, h // G, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, s, t: (b, h, s, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
