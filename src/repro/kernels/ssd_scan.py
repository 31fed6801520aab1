"""Chunked scalar-decay SSD (Mamba2 / mLSTM-style linear recurrence) as a
Pallas TPU kernel.

This is the compute substrate for the ssm/hybrid architectures (xlstm,
zamba2) — the layer that makes ``long_500k`` decode and 4k training
tractable.  TPU adaptation of the SSD algorithm (not a CUDA port):

  * grid = (batch, head, chunks) with chunks innermost: the inter-chunk
    recurrent state h [dh, ds] persists in VMEM scratch across the
    sequential chunk axis — zero HBM traffic for the recurrence;
  * the intra-chunk term is two MXU contractions ([Q x ds] @ [ds x Q] decay-
    masked, then [Q x Q] @ [Q x dh]) on 128-aligned tiles — the quadratic
    work is what the MXU is for, the scan only carries the tiny state;
  * cumulative log-decays are computed in fp32 inside the kernel; the decay
    mask exp(A_i - A_j) * tril is fused with the C·B score matrix.

Layouts expected by the kernel (the ops wrapper rearranges):
  xb  [B, H, S, dh]   dt-scaled inputs
  Bm  [B, S, ds]      input projections (shared across heads)
  Cm  [B, S, ds]
  ld  [B, H, S]       log decays (negative)
Returns y [B, H, S, dh] and final state h [B, H, dh, ds], both fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(xb_ref, b_ref, c_ref, ld_ref, y_ref, h_out_ref, h_ref,
            *, chunk: int, nchunks: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xb = xb_ref[0, 0].astype(jnp.float32)               # [Q, dh]
    Bm = b_ref[0].astype(jnp.float32)                   # [Q, ds]
    Cm = c_ref[0].astype(jnp.float32)                   # [Q, ds]
    ld = ld_ref[0, 0].astype(jnp.float32)               # [Q]

    A = jnp.cumsum(ld)                                  # [Q]
    A_tot = A[-1]

    # intra-chunk: scores[i,j] = (C_i . B_j) * exp(A_i - A_j), j <= i
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # [Q, Q]
    dec = A[:, None] - A[None, :]
    tril = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    w = jnp.where(tril, jnp.exp(dec), 0.0)
    y_intra = jax.lax.dot_general(cb * w, xb, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: y_inter[i] = exp(A_i) * C_i . h_prev^T
    h_prev = h_ref[...]                                 # [dh, ds]
    y_inter = jax.lax.dot_general(Cm, h_prev, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_ref[0, 0] = (y_intra + y_inter * jnp.exp(A)[:, None]).astype(y_ref.dtype)

    # state update: h = exp(A_tot) h_prev + xb^T @ (B * exp(A_tot - A))
    wj = jnp.exp(A_tot - A)[:, None] * Bm               # [Q, ds]
    h_new = jnp.exp(A_tot) * h_prev + jax.lax.dot_general(
        xb, wj, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    h_ref[...] = h_new

    @pl.when(k == nchunks - 1)
    def _final():
        h_out_ref[0, 0] = h_new


def ssd_scan(xb, Bm, Cm, ld, chunk: int = 128,
             interpret: bool | None = None):
    """xb: [B, H, S, dh]; Bm, Cm: [B, S, ds]; ld: [B, H, S].

    Returns (y [B, H, S, dh] fp32, h_final [B, H, dh, ds] fp32)."""
    B, H, S, dh = xb.shape
    ds = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    K = S // Q
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    kernel = functools.partial(_kernel, chunk=Q, nchunks=K)
    y, h = pl.pallas_call(
        kernel,
        grid=(B, H, K),
        in_specs=[
            pl.BlockSpec((1, 1, Q, dh), lambda b, h, k: (b, h, k, 0)),
            pl.BlockSpec((1, Q, ds), lambda b, h, k: (b, k, 0)),
            pl.BlockSpec((1, Q, ds), lambda b, h, k: (b, k, 0)),
            pl.BlockSpec((1, 1, Q), lambda b, h, k: (b, h, k)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, dh), lambda b, h, k: (b, h, k, 0)),
            pl.BlockSpec((1, 1, dh, ds), lambda b, h, k: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H, dh, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, ds), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(xb, Bm, Cm, ld)
    return y, h
