"""jit'd wrappers around the Pallas kernels + XLA fallbacks.

The model layer calls these when ``cfg.attn_impl`` resolves to Pallas
(``models.config.use_pallas``: on a TPU by default, or when a test pins
``attn_impl='pallas'`` to run the kernels in interpret mode on the CPU);
``impl='xla'`` selects the pure-jnp reference instead.

Wrappers own the layout glue (head-major transposes of the dense caches,
block-size selection, shape-divisibility fallbacks) so kernels stay minimal.

Under a ShardingPlan the attention entry points accept ``mesh=``: a
Pallas call traced inside GSPMD-partitioned jit code would make XLA
replicate its operands (the kernel is a partitioning black box), so the
wrappers shard_map themselves over the mesh's 'model' axis instead —
each device runs the un-partitioned kernel on its contiguous HEAD slice
(q heads and KV heads split together, so GQA's ``h -> h // group``
mapping stays local to the shard).  Shapes the head axes cannot split
evenly fall back to the XLA reference, which GSPMD partitions like any
other jnp code.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref


def _model_shards(mesh, *head_counts) -> int:
    """How many ways to shard_map over 'model' (1 = don't wrap)."""
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    n = mesh.shape["model"]
    if n <= 1 or any(h % n for h in head_counts):
        return 1
    return n
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.paged_decode_attention import (
    paged_decode_attention as _paged_decode_pallas)
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas


def _pick_block(S: int, want: int = 128) -> int:
    """The largest tile of at most ``want`` rows that divides ``S`` and that
    the TPU tiling accepts: a multiple of 8, or else the whole axis."""
    for b in range(min(want, S) // 8 * 8, 0, -8):
        if S % b == 0:
            return b
    return S


def flash_attention(q, k, v, causal: bool = True, softcap: float = 0.0,
                    impl: str = "pallas", mesh=None):
    """q: [B, H, S, d]; k,v: [B, KV, T, d] -> [B, H, S, d]."""
    if impl == "xla" or (softcap > 0):
        return ref.flash_attention_ref(q, k, v, causal=causal, softcap=softcap)
    if _model_shards(mesh, q.shape[1], k.shape[1]) > 1:
        hs = P(None, "model", None, None)        # split the head axis
        return jax.shard_map(
            lambda qs, ks, vs: flash_attention(qs, ks, vs, causal=causal,
                                               softcap=softcap, impl=impl),
            mesh=mesh, in_specs=(hs, hs, hs), out_specs=hs,
            check_vma=False)(q, k, v)
    bq = _pick_block(q.shape[2])
    bk = _pick_block(k.shape[2])
    return _flash_pallas(q, k, v, causal=causal, block_q=bq, block_k=bk)


def decode_attention(q, k, v, length, impl: str = "pallas", mesh=None):
    """q: [B, H, d]; k,v: [B, KV, T, d] -> [B, H, d]."""
    if impl == "xla":
        return ref.decode_attention_ref(q, k, v, length)
    if _model_shards(mesh, q.shape[1], k.shape[1]) > 1:
        # dense-pool decode under TP: heads split over 'model', the
        # per-sequence lengths are replicated control state (broadcast to
        # [B] OUTSIDE shard_map so every shard sees the same vector)
        length = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1),
                                  (q.shape[0],))
        hs = P(None, "model", None, None)
        return jax.shard_map(
            lambda qs, ks, vs, ln: decode_attention(qs, ks, vs, ln,
                                                    impl=impl),
            mesh=mesh,
            in_specs=(P(None, "model", None), hs, hs, P(None)),
            out_specs=P(None, "model", None),
            check_vma=False)(q, k, v, length)
    bk = _pick_block(k.shape[2], want=256)
    return _decode_pallas(q, k, v, length, block_k=bk)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                           k_scales=None, v_scales=None,
                           impl: str = "pallas", mesh=None):
    """q: [B, H, d]; k_pages, v_pages: [P, ps, KV, d] (the page arena in the
    model's storage layout); page_table: [B, NB]; lengths: scalar or [B];
    k_scales, v_scales: optional [P, ps, KV] per-row scales for an int8
    arena (dequantized inside the kernel).  Returns [B, H, d].

    The Pallas kernel reads the arena as stored, with no transpose: its
    grid is (B, blocks), a block ``ppb`` pages of every KV head, each page
    ``[ps, KV, d]`` one DMA steered by the page table, and blocks past a
    row's length issue no DMA.  ``ppb`` follows from the shapes: pages
    for 256 keys within a fixed VMEM budget
    (``paged_decode_attention._pages_per_block``)."""
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    if impl == "xla":
        return ref.paged_decode_attention_ref(q, k_pages, v_pages,
                                              page_table, lengths,
                                              k_scales=k_scales,
                                              v_scales=v_scales)
    if _model_shards(mesh, q.shape[1], k_pages.shape[2]) > 1:
        # the arena's KV-head axis carries the plan's 'model' placement
        # (paged_cache_specs), so each shard attends its own head slice
        # against locally-resident pages; the page table and lengths are
        # replicated host-driven control state
        if k_scales is None:
            return jax.shard_map(
                lambda qs, ks, vs, pt, ln: paged_decode_attention(
                    qs, ks, vs, pt, ln, impl=impl),
                mesh=mesh,
                in_specs=(P(None, "model", None),
                          P(None, None, "model", None),
                          P(None, None, "model", None), P(), P()),
                out_specs=P(None, "model", None),
                check_vma=False)(q, k_pages, v_pages, page_table, lengths)
        # scale arenas shard with their value leaves' KV-head axis (or sit
        # replicated if paged_cache_specs couldn't split it — but head
        # divisibility was just checked, so 'model' applies here)
        return jax.shard_map(
            lambda qs, ks, vs, ksc, vsc, pt, ln: paged_decode_attention(
                qs, ks, vs, pt, ln, k_scales=ksc, v_scales=vsc, impl=impl),
            mesh=mesh,
            in_specs=(P(None, "model", None), P(None, None, "model", None),
                      P(None, None, "model", None), P(None, None, "model"),
                      P(None, None, "model"), P(), P()),
            out_specs=P(None, "model", None),
            check_vma=False)(q, k_pages, v_pages, k_scales, v_scales,
                             page_table, lengths)
    return _paged_decode_pallas(q, k_pages, v_pages, page_table, lengths,
                                k_scales=k_scales, v_scales=v_scales)


def fused_rmsnorm(x, scale, eps: float = 1e-6, impl: str = "pallas"):
    if impl == "xla":
        return ref.rmsnorm_ref(x, scale, eps)
    return _rmsnorm_pallas(x, scale, eps)


def ssd_scan(xb, Bm, Cm, ld, chunk: int = 128, impl: str = "pallas"):
    """xb: [B, H, S, dh] head-major.  Returns (y [B,H,S,dh], h [B,H,dh,ds])."""
    if impl == "xla":
        y, h = ref.ssd_scan_ref(jnp.moveaxis(xb, 1, 2), Bm, Cm,
                                jnp.moveaxis(ld, 1, 2))
        return jnp.moveaxis(y, 1, 2), h
    return _ssd_pallas(xb, Bm, Cm, ld, chunk=chunk)
