"""Paged flash-decoding over a block-paged KV cache as a Pallas TPU kernel.

The serving runtime stores KV state in one shared page arena instead of a
dense per-slot cache (vLLM/PagedAttention layout): a request's cache is a
page table of fixed-size blocks, so HBM holds the tokens that exist, not
``n_slots * max_len`` worst cases.  The kernel reads that arena in place,
in the model's storage layout ``[P, ps, KV, d]``: one page ``[ps, KV, d]``
is contiguous and holds every KV head.

  * grid = (batch, blocks), blocks innermost and sequential.  A block is
    ``ppb`` consecutive logical pages of one row, for every KV head.  The
    online-softmax state of each head's [G, d] query group lives in VMEM
    scratch across the row's blocks;
  * K and V stay in HBM (``memory_space=pl.ANY``).  The page table and the
    per-row lengths ride in as SCALAR-PREFETCH arguments
    (``pltpu.PrefetchScalarGridSpec``), and each block issues one DMA per
    page, aimed by ``page_table[b, ·]``, into a double-buffered VMEM
    scratch.  A block starts the copies of the next live block (possibly
    the next row's first) before it computes, so the copies overlap the
    compute;
  * only live blocks run: a block wholly past the row's length issues no
    DMA and no compute, the tail block copies only the pages that hold
    keys and masks past the length, so a dummy row (one key on the null
    page) costs one page;
  * per KV head, the [G, d] x [d, ppb*ps] scores, fp32 online softmax and
    fp32 accumulation, output cast to the query dtype.

``ppb`` follows from the shapes alone (``_pages_per_block``): enough pages
for ``BLOCK_TOKENS`` keys, fewer where the double-buffered K and V blocks,
as the TPU tiles them, would pass ``VMEM_BUDGET``.

A page DMA must move whole HBM tiles, so the arena is read in place where
a page's two minor axes ``(KV, d)`` are whole tiles (d a multiple of 128
and, below 32 bits, KV a multiple of 8: qwen3-14b's ``[8, 128]``).  Other
shapes are padded to whole tiles first (``_tile_pad``), a copy per call.

Quantized arenas (int8 values + per-row float32 scales ``[P, ps, KV]``,
padded to 128 lanes) copy each page's scales beside its values, and the
scales fold into the score and probability tiles (see ``_accumulate``):
fp32 K/V is never materialized in HBM.

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
BLOCK_TOKENS = 256            # keys per block the page count aims for
VMEM_BUDGET = 4 << 20         # bytes for the double-buffered K and V blocks


def _pages_per_block(NB: int, ps: int, KV: int, d: int,
                     itemsize: int) -> int:
    """Pages per block: ``BLOCK_TOKENS // ps``, at most the table's width,
    and at most what fits ``VMEM_BUDGET`` with K and V double-buffered.  A
    page is counted as the TPU tiles it in VMEM: KV rows padded to the
    dtype's sublane tile (8 rows of 32 bits), d padded to 128 lanes."""
    rows = 32 // itemsize
    page = ps * -(-KV // rows) * rows * -(-d // 128) * 128 * itemsize
    return max(1, min(NB, BLOCK_TOKENS // ps, VMEM_BUDGET // (4 * page)))


def _tile_pad(x):
    """``x`` with its two minor axes padded to whole HBM tiles, as the
    TPU's DMA of one page needs: the minor axis to 128 lanes, and the
    second-minor axis of a sub-32-bit dtype to 8 rows.  Returns ``x``
    itself, read in place, where both already are whole tiles."""
    rows = 1 if x.dtype.itemsize == 4 else 8
    pad = [(0, 0)] * (x.ndim - 2) + [(0, -x.shape[-2] % rows),
                                     (0, -x.shape[-1] % 128)]
    return jnp.pad(x, pad) if any(hi for _, hi in pad) else x


def _accumulate(q, k, v, valid, scale, acc_ref, m_ref, l_ref,
                k_row_scale=None, v_row_scale=None):
    """One online-softmax step of one head over a [T, d] K/V block (fp32).

    ``valid`` [1, T] marks the keys within the row's length; the others
    may be stale or never-copied VMEM and are masked out of the scores and
    the probabilities.  ``k_row_scale``/``v_row_scale`` ([1, T], int8
    arenas) scale each K/V row.  A row scale of K is a column scale of the
    [G, T] scores, and a row scale of V is a column scale of the
    probabilities it multiplies, so both apply lane-wise to [G, T] tiles
    and the int8 block is never rescaled row by row.
    """
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # [G, T]
    if k_row_scale is not None:
        scores = scores * k_row_scale
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = p if v_row_scale is None else jnp.where(valid, p * v_row_scale, 0.)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _kernel(pt_ref, len_ref, q_ref, *refs, scale: float, ps: int, ppb: int,
            nb: int, n_rows: int, quantized: bool):
    """Grid point (b, j): fold block ``j`` of row ``b`` into its output.

    ``refs`` holds the HBM arenas (K, V, then the K and V scales of an
    int8 arena), the output block, one [2, ppb, ps, KV, ...] VMEM buffer
    and one DMA semaphore pair per arena, the [KV, G, d] fp32 accumulator,
    the [KV, G, 1] running max and normalizer, and two SMEM words: the
    buffer slot the current block lands in, and whether any copy was
    started yet.  The accumulators are initialized at j == 0 and emitted
    at the row's last grid step.
    """
    n_in = 4 if quantized else 2
    hbm, o_ref = refs[:n_in], refs[n_in]
    bufs = refs[n_in + 1:2 * n_in + 1]
    sems = refs[2 * n_in + 1:3 * n_in + 1]
    acc_ref, m_ref, l_ref, slot_ref, started_ref = refs[3 * n_in + 1:]
    KV = q_ref.shape[1]
    d = q_ref.shape[3]
    bk = ppb * ps
    b, j = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]

    def block_copies(row, blk, slot, method):
        """Start (or wait for) the page DMAs of block ``blk`` of ``row``:
        one per page the row's length reaches, none past it."""
        live = jnp.minimum(
            jnp.minimum(pl.cdiv(len_ref[row], ps), nb) - blk * ppb, ppb)

        def page(i, carry):
            src_page = pt_ref[row, blk * ppb + i]
            for src, buf, sem in zip(hbm, bufs, sems):
                getattr(pltpu.make_async_copy(
                    src.at[src_page], buf.at[slot, i], sem.at[slot]),
                    method)()
            return carry

        jax.lax.fori_loop(0, live, page, 0)

    @pl.when((b == 0) & (j == 0))
    def _reset():
        slot_ref[0] = 0
        started_ref[0] = 0

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * bk < length)
    def _compute():
        slot = slot_ref[0]

        @pl.when(started_ref[0] == 0)
        def _first():
            block_copies(b, j, slot, "start")
            started_ref[0] = 1

        # the next live block: this row's next one, else block 0 of the
        # next row that holds a key
        more = (j + 1) * bk < length
        row = jax.lax.fori_loop(
            b + 1, n_rows,
            lambda r, f: jnp.where((f == n_rows) & (len_ref[r] > 0), r, f),
            jnp.int32(n_rows))
        nxt_b = jnp.where(more, b, row)
        nxt_j = jnp.where(more, j + 1, 0)

        @pl.when(more | (row < n_rows))
        def _prefetch():
            block_copies(nxt_b, nxt_j, 1 - slot, "start")
            slot_ref[0] = 1 - slot

        block_copies(b, j, slot, "wait")
        pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        valid = pos < length                                   # [1, T]
        # keys past the length may sit in never-copied VMEM: zero their V
        # rows, since a zero probability times a NaN is still NaN
        valid_rows = (j * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bk, 1), 0)) < length                   # [T, 1]
        if quantized:       # scale pages [ps, KV padded to 128 lanes]
            k_rs = bufs[2][slot].reshape(bk, -1).T             # [128, T]
            v_rs = bufs[3][slot].reshape(bk, -1).T
        for h in range(KV):
            q = q_ref[0, h].astype(jnp.float32)                # [G, d]
            k = bufs[0][slot, :, :, h, :].astype(jnp.float32).reshape(bk, d)
            v = bufs[1][slot, :, :, h, :].astype(jnp.float32).reshape(bk, d)
            v = jnp.where(valid_rows, v, 0.)
            _accumulate(q, k, v, valid, scale, acc_ref.at[h], m_ref.at[h],
                        l_ref.at[h],
                        k_row_scale=k_rs[h:h + 1] if quantized else None,
                        v_row_scale=v_rs[h:h + 1] if quantized else None)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           k_scales=None, v_scales=None,
                           interpret: bool | None = None):
    """Single-token attention against the page arena as the model stores it.

    Args:
      q: [B, H, d] query block (one decode token per sequence).
      k_pages, v_pages: [P, ps, KV, d] page arena in the storage layout
        (int8 when scales are given, any fp dtype otherwise).
      page_table: [B, NB] int32 physical page per logical block.
      lengths: scalar or [B] valid positions per sequence.
      k_scales, v_scales: optional [P, ps, KV] float32 per-row scales;
        both or neither — selects the in-kernel dequantizing variant.
      interpret: force Pallas interpret mode (defaults to CPU backend).

    Returns:
      [B, H, d] attention output in ``q.dtype``.
    """
    B, H, d = q.shape
    _, ps, KV, _ = k_pages.shape
    NB = page_table.shape[1]
    assert H % KV == 0
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    quantized = k_scales is not None
    G = H // KV
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    arenas = [_tile_pad(k_pages), _tile_pad(v_pages)]
    if quantized:
        arenas += [_tile_pad(k_scales.astype(jnp.float32)),
                   _tile_pad(v_scales.astype(jnp.float32))]
    KVp, dp = arenas[0].shape[2:]
    ppb = _pages_per_block(NB, ps, KVp, dp, k_pages.dtype.itemsize)

    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                               (B,))
    page_table = jnp.asarray(page_table, jnp.int32)
    qg = q.reshape(B, KV, G, d)
    if dp != d:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
    group = pl.BlockSpec((1, KV, G, dp), lambda b, j, pt, ln: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # page table + lengths
        grid=(B, pl.cdiv(NB, ppb)),
        in_specs=[group] + [pl.BlockSpec(memory_space=pl.ANY)] * len(arenas),
        out_specs=group,
        scratch_shapes=(
            [pltpu.VMEM((2, ppb) + a.shape[1:], a.dtype) for a in arenas]
            + [pltpu.SemaphoreType.DMA((2,))] * len(arenas)
            + [pltpu.VMEM((KV, G, dp), jnp.float32),
               pltpu.VMEM((KV, G, 1), jnp.float32),
               pltpu.VMEM((KV, G, 1), jnp.float32),
               pltpu.SMEM((1,), jnp.int32),
               pltpu.SMEM((1,), jnp.int32)]),
    )
    kernel = functools.partial(_kernel, scale=1.0 / np.sqrt(d), ps=ps,
                               ppb=ppb, nb=NB, n_rows=B, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, dp), q.dtype),
        # a live block starts the next live block's copies, across rows
        # too, so both axes run in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table, lengths, qg, *arenas)
    return out[..., :d].reshape(B, H, d)
