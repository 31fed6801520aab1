"""Paged flash-decoding over a block-paged KV cache as a Pallas TPU kernel.

The serving runtime stores KV state in one shared page arena instead of a
dense per-slot cache (vLLM/PagedAttention layout): a request's cache is a
page table of fixed-size blocks, so HBM holds the tokens that exist, not
``n_slots * max_len`` worst cases.  The kernel design follows
``decode_attention.py``:

  * grid = (batch, kv_head, logical_blocks), blocks innermost (sequential
    on-core) so the online-softmax state for the [G, d] query-group tile
    lives in VMEM scratch across the whole pass;
  * the page table and per-sequence lengths ride in as SCALAR-PREFETCH
    arguments (``pltpu.PrefetchScalarGridSpec``): the K/V index maps read
    ``page_table[b, t]`` to aim each block's HBM->VMEM DMA at the right
    physical page — the gather never materializes a dense [B, T] cache;
  * blocks entirely past a sequence's length are structurally skipped via
    ``pl.when``; the tail block is masked elementwise;
  * fp32 accumulation, output cast to the query dtype.

Quantized arenas (int8 values + per-row float32 scales) use the dequant
variant: the scale pages ride the SAME scalar-prefetch steering as the
K/V pages — their BlockSpec index maps read ``page_table[b, t]`` too — and
each block is dequantized in VMEM (``int8 * scale`` per row) right before
the online-softmax accumulation.  fp32 K/V is never materialized in HBM.

Validated in interpret mode on CPU against ``ref.paged_decode_attention_ref``
(see tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)


def _accumulate(q, k, v, t, length, scale, ps, acc_ref, m_ref, l_ref,
                k_row_scale=None, v_row_scale=None):
    """One online-softmax step over a [ps, d] K/V block (fp32 in VMEM).

    ``k_row_scale``/``v_row_scale`` ([1, ps], int8 arenas) scale each K/V
    row.  A row scale of K is a column scale of the [G, ps] scores, and a
    row scale of V is a column scale of the probabilities it multiplies,
    so both apply lane-wise to [G, ps] tiles and the int8 block is never
    rescaled row by row.
    """
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # [G, ps]
    if k_row_scale is not None:
        scores = scores * k_row_scale
    cols = t * ps + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(cols < length, scores, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = p if v_row_scale is None else p * v_row_scale
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
            l_ref, *, scale: float, ps: int, nb: int):
    """Grid point (b, h, t): fold page ``page_table[b, t]`` into (b, h).

    Scratch: ``acc_ref`` [G, d] fp32 accumulator, ``m_ref``/``l_ref``
    [G, 1] running max / normalizer — persistent across the innermost
    (sequential) block axis, initialized at t == 0, emitted at t == nb-1.
    """
    b = pl.program_id(0)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    # skip logical blocks entirely past the valid region (their page-table
    # entries point at the null page; nothing to read)
    @pl.when(t * ps < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [G, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [ps, d]
        v = v_ref[0, 0].astype(jnp.float32)            # [ps, d]
        _accumulate(q, k, v, t, length, scale, ps, acc_ref, m_ref, l_ref)

    @pl.when(t == nb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _dequant_kernel(pt_ref, len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                    o_ref, acc_ref, m_ref, l_ref, *, scale: float, ps: int,
                    nb: int):
    """Like ``_kernel`` but K/V blocks arrive int8 with per-row scales.

    ``ks_ref``/``vs_ref`` are [1, 1, 1, ps] float32 scale blocks steered
    by the same ``page_table[b, t]`` index map as their value blocks; the
    scales fold into the score and probability tiles (see
    ``_accumulate``), so fp K/V is never materialized.
    """
    b = pl.program_id(0)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    @pl.when(t * ps < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                      # [G, d]
        k = k_ref[0, 0].astype(jnp.float32)                      # [ps, d]
        v = v_ref[0, 0].astype(jnp.float32)
        _accumulate(q, k, v, t, length, scale, ps, acc_ref, m_ref, l_ref,
                    k_row_scale=ks_ref[0, 0], v_row_scale=vs_ref[0, 0])

    @pl.when(t == nb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           k_scales=None, v_scales=None,
                           interpret: bool | None = None):
    """Single-token attention against a head-major page arena.

    Args:
      q: [B, H, d] query block (one decode token per sequence).
      k_pages, v_pages: [P, KV, ps, d] head-major page arena (int8 when
        scales are given, any fp dtype otherwise).
      page_table: [B, NB] int32 physical page per logical block.
      lengths: scalar or [B] valid positions per sequence.
      k_scales, v_scales: optional [P, KV, ps] float32 per-row scales;
        both or neither — selects the in-kernel dequantizing variant.
      interpret: force Pallas interpret mode (defaults to CPU backend).

    Returns:
      [B, H, d] attention output in ``q.dtype``.
    """
    B, H, d = q.shape
    P, KV, ps, _ = k_pages.shape
    NB = page_table.shape[1]
    assert H % KV == 0
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    G = H // KV
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    scale = 1.0 / np.sqrt(d)

    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                               (B,))
    page_table = jnp.asarray(page_table, jnp.int32)
    qg = q.reshape(B, KV, G, d)

    q_spec = pl.BlockSpec((1, 1, G, d), lambda b, h, t, pt, ln: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, ps, d),
                           lambda b, h, t, pt, ln: (pt[b, t], h, 0, 0))
    if k_scales is None:
        kernel = functools.partial(_kernel, scale=scale, ps=ps, nb=NB)
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (page_table, lengths, qg, k_pages, v_pages)
    else:
        # a unit axis before the in-page axis makes the scale block's last
        # two dims (1, ps) equal the array's, as the TPU tiling requires
        scale_spec = pl.BlockSpec((1, 1, 1, ps),
                                  lambda b, h, t, pt, ln: (pt[b, t], h, 0, 0))
        kernel = functools.partial(_dequant_kernel, scale=scale, ps=ps,
                                   nb=NB)
        in_specs = [q_spec, kv_spec, scale_spec, kv_spec, scale_spec]
        operands = (page_table, lengths, qg,
                    k_pages, k_scales.astype(jnp.float32)[:, :, None],
                    v_pages, v_scales.astype(jnp.float32)[:, :, None])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # page table + lengths
        grid=(B, KV, NB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, d),
                               lambda b, h, t, pt, ln: (b, h, 0, 0)),
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
        name="paged_decode_attention",
    )(*operands)
    return out.reshape(B, H, d)
