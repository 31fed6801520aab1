"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles
(interpret mode on CPU), hypothesis property sweeps, and end-to-end model
integration via cfg.attn_impl='pallas'."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan
from repro.models.registry import get_smoke_model

RNG = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,S,d", [
    (1, 4, 4, 128, 64),      # MHA
    (2, 8, 2, 128, 64),      # GQA 4:1
    (1, 4, 1, 256, 128),     # MQA, big head
    (1, 8, 8, 64, 32),       # small
])
def test_flash_attention_sweep(B, H, KV, S, d, dtype):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, H, S, d), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, d), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, d), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_non_causal():
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@given(bq=st.sampled_from([32, 64, 128]), bk=st.sampled_from([32, 64, 128]))
@settings(max_examples=6, deadline=None)
def test_flash_attention_block_shape_invariance(bq, bk):
    """Output must not depend on the VMEM tiling."""
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64))
    k = jax.random.normal(ks[1], (1, 2, 128, 64))
    v = jax.random.normal(ks[2], (1, 2, 128, 64))
    out = flash_attention(q, k, v, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,T,d", [
    (2, 8, 2, 512, 64),
    (1, 4, 4, 256, 128),
    (2, 8, 1, 1024, 64),
])
def test_decode_attention_sweep(B, H, KV, T, d, dtype):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, H, d), dtype)
    k = jax.random.normal(ks[1], (B, KV, T, d), dtype)
    v = jax.random.normal(ks[2], (B, KV, T, d), dtype)
    lengths = jnp.asarray([T // 3, T][:B])
    out = decode_attention(q, k, v, lengths, block_k=128)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@given(length=st.integers(1, 512))
@settings(max_examples=10, deadline=None)
def test_decode_attention_any_length(length):
    """Masking must be exact for every cache occupancy."""
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, 4, 2, 64))[:, :, 0]
    k = jax.random.normal(ks[1], (1, 4, 512, 64))
    v = jax.random.normal(ks[2], (1, 4, 512, 64))
    out = decode_attention(q, k, v, length, block_k=128)
    want = ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_decode_attention_per_sequence_lengths():
    """Scalar-prefetched lengths: every sequence of the batch masks at its
    own length (smollm-135m decode widths: 9 heads over 3 KV heads)."""
    B, H, KV, T, d = 4, 9, 3, 512, 64
    ks = jax.random.split(jax.random.fold_in(RNG, 41), 3)
    q = jax.random.normal(ks[0], (B, H, d))
    k = jax.random.normal(ks[1], (B, KV, T, d))
    v = jax.random.normal(ks[2], (B, KV, T, d))
    lengths = jnp.asarray([1, 130, T - 1, T], jnp.int32)
    out = decode_attention(q, k, v, lengths, block_k=128)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

def _page_arena(key, B, KV, d, ps, NB, n_pages):
    """Random arena + disjoint per-sequence page tables (page 0 = null)."""
    ks = jax.random.split(key, 3)
    kp = jax.random.normal(ks[0], (n_pages, ps, KV, d))
    vp = jax.random.normal(ks[1], (n_pages, ps, KV, d))
    perm = np.asarray(jax.random.permutation(ks[2], n_pages - 1) + 1)
    pt = np.zeros((B, NB), np.int32)
    flat = perm[:B * NB].reshape(B, NB)
    pt[:, :] = flat
    return kp, vp, jnp.asarray(pt)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,ps,NB,d", [
    (2, 8, 2, 64, 8, 64),       # GQA 4:1
    (1, 4, 4, 128, 4, 128),     # MHA, big pages
    (3, 8, 1, 16, 6, 64),       # MQA, small pages
])
def test_paged_decode_attention_sweep(B, H, KV, ps, NB, d, dtype):
    n_pages = B * NB + 1
    kp, vp, pt = _page_arena(RNG, B, KV, d, ps, NB, n_pages)
    kp, vp = kp.astype(dtype), vp.astype(dtype)
    q = jax.random.normal(jax.random.fold_in(RNG, 9), (B, H, d), dtype)
    lengths = jnp.asarray([(NB * ps) // (i + 1) for i in range(B)], jnp.int32)
    out = ops.paged_decode_attention(q, kp, vp, pt, lengths)
    want = ref.paged_decode_attention_ref(q, kp, vp, pt, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_paged_matches_dense_decode():
    """Scattering a dense cache into pages (in any physical order) must
    reproduce the dense decode kernel's result exactly."""
    B, H, KV, T, d, ps = 2, 8, 2, 256, 64, 64
    NB = T // ps
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, H, d))
    k = jax.random.normal(ks[1], (B, KV, T, d))
    v = jax.random.normal(ks[2], (B, KV, T, d))
    # scatter each sequence's blocks into a shuffled shared arena
    n_pages = B * NB + 1
    perm = np.asarray(jax.random.permutation(jax.random.fold_in(RNG, 3),
                                             n_pages - 1) + 1)
    pt = perm[:B * NB].reshape(B, NB)
    kp = np.zeros((n_pages, ps, KV, d), np.float32)
    vp = np.zeros((n_pages, ps, KV, d), np.float32)
    kb = np.asarray(k).transpose(0, 2, 1, 3).reshape(B, NB, ps, KV, d)
    vb = np.asarray(v).transpose(0, 2, 1, 3).reshape(B, NB, ps, KV, d)
    for b in range(B):
        for j in range(NB):
            kp[pt[b, j]] = kb[b, j]
            vp[pt[b, j]] = vb[b, j]
    lengths = jnp.asarray([T - 7, T // 2], jnp.int32)
    dense = ops.decode_attention(q, k, v, lengths)
    paged = ops.paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                       jnp.asarray(pt), lengths)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               atol=2e-5)


@given(length=st.integers(1, 256))
@settings(max_examples=10, deadline=None)
def test_paged_decode_any_length(length):
    """Tail-block masking must be exact for every cache occupancy."""
    B, KV, d, ps, NB = 1, 4, 64, 32, 8
    kp, vp, pt = _page_arena(jax.random.fold_in(RNG, 17), B, KV, d, ps, NB,
                             B * NB + 1)
    q = jax.random.normal(jax.random.fold_in(RNG, 23), (B, 4, d))
    out = ops.paged_decode_attention(q, kp, vp, pt, length)
    want = ref.paged_decode_attention_ref(q, kp, vp, pt, length)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("B,H,KV,ps,NB,d", [
    (2, 8, 2, 64, 8, 64),       # GQA 4:1
    (3, 8, 1, 16, 6, 64),       # MQA, small pages
])
def test_paged_decode_dequant_sweep(B, H, KV, ps, NB, d):
    """The in-kernel dequantizing variant must match the dequant oracle."""
    from repro.models.quant import quantize_rows

    n_pages = B * NB + 1
    kp, vp, pt = _page_arena(RNG, B, KV, d, ps, NB, n_pages)
    kq, ks = quantize_rows(kp)
    vq, vs = quantize_rows(vp)
    q = jax.random.normal(jax.random.fold_in(RNG, 29), (B, H, d))
    lengths = jnp.asarray([(NB * ps) // (i + 1) for i in range(B)], jnp.int32)
    out = ops.paged_decode_attention(q, kq, vq, pt, lengths,
                                     k_scales=ks, v_scales=vs)
    want = ref.paged_decode_attention_ref(q, kq, vq, pt, lengths,
                                          k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_paged_decode_dequant_close_to_fp():
    """int8 round-tripped attention stays close to the fp arena's output.

    Per-row absmax scales bound the element error at ~scale/2, so the
    attention output over a normal(0,1) arena lands within ~1e-2.
    """
    from repro.models.quant import quantize_rows

    B, H, KV, ps, NB, d = 2, 8, 2, 32, 4, 64
    kp, vp, pt = _page_arena(jax.random.fold_in(RNG, 31), B, KV, d, ps, NB,
                             B * NB + 1)
    kq, ks = quantize_rows(kp)
    vq, vs = quantize_rows(vp)
    q = jax.random.normal(jax.random.fold_in(RNG, 37), (B, H, d))
    lengths = jnp.asarray([NB * ps, ps + 3], jnp.int32)
    fp = ops.paged_decode_attention(q, kp, vp, pt, lengths)
    quant = ops.paged_decode_attention(q, kq, vq, pt, lengths,
                                       k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(quant), np.asarray(fp), atol=3e-2)


def test_paged_decode_dequant_unit_scale_axis():
    """Each page's scales (copied beside its values, padded to whole tiles)
    must steer like its values: scales that differ per page, row and head,
    at smollm-135m decode widths."""
    from repro.models.quant import quantize_rows

    B, H, KV, ps, NB, d = 3, 9, 3, 16, 4, 64
    kp, vp, pt = _page_arena(jax.random.fold_in(RNG, 43), B, KV, d, ps, NB,
                             B * NB + 1)
    rows = jax.random.uniform(jax.random.fold_in(RNG, 47),
                              kp.shape[:-1] + (1,), minval=0.1, maxval=4.0)
    kq, ks = quantize_rows(kp * rows)
    vq, vs = quantize_rows(vp / rows)
    q = jax.random.normal(jax.random.fold_in(RNG, 53), (B, H, d))
    lengths = jnp.asarray([NB * ps, ps + 5, 1], jnp.int32)
    out = ops.paged_decode_attention(q, kq, vq, pt, lengths,
                                     k_scales=ks, v_scales=vs)
    want = ref.paged_decode_attention_ref(q, kq, vq, pt, lengths,
                                          k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def _edge_rows(key, KV, d, ps, NB, lengths, int8):
    """An arena of ``len(lengths)`` rows on shuffled pages plus one dummy
    row (one key on the null page, page 0), as the engine's idle slots."""
    from repro.models.quant import quantize_rows

    B = len(lengths) + 1
    kp, vp, pt = _page_arena(key, B, KV, d, ps, NB, B * NB + 1)
    pt = pt.at[-1].set(0)
    lengths = jnp.asarray(list(lengths) + [1], jnp.int32)
    if not int8:
        return kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16), pt, \
            lengths, {}
    kq, ks = quantize_rows(kp)
    vq, vs = quantize_rows(vp)
    return kq, vq, pt, lengths, dict(k_scales=ks, v_scales=vs)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("KV,G,d", [
    (2, 5, 128),                # qwen3-14b's head group, two KV heads
    (4, 1, 128),                # G 1: one query per KV head (MHA)
    (1, 8, 64),                 # G 8, MQA, d padded to 128 lanes
])
def test_paged_decode_block_edges(KV, G, d, int8):
    """Pages of 8 give blocks of 32 pages and 40 logical pages two blocks,
    the second short.  Rows end at 1 key, one page, one block, one block
    and a key, and the whole table; a dummy row sits on the null page."""
    from repro.kernels.paged_decode_attention import _pages_per_block

    ps, NB = 8, 40
    ppb = _pages_per_block(NB, ps, KV, 128, 1 if int8 else 2)
    assert ppb == 32 and NB % ppb
    kp, vp, pt, lengths, scales = _edge_rows(
        jax.random.fold_in(RNG, 59), KV, d, ps, NB,
        [1, ps, ppb * ps, ppb * ps + 1, NB * ps], int8)
    q = jax.random.normal(jax.random.fold_in(RNG, 61),
                          (len(lengths), KV * G, d), jnp.bfloat16)
    out = ops.paged_decode_attention(q, kp, vp, pt, lengths, **scales)
    want = ref.paged_decode_attention_ref(q, kp, vp, pt, lengths, **scales)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               **_tol(jnp.bfloat16))


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_paged_decode_reads_the_arena_in_place(int8):
    """The wrapper hands the kernel the arena as stored: no transpose of an
    arena-shaped operand anywhere in the traced program (the kernel's body
    included), at qwen3-14b's aligned page [8, 8, 128]."""
    kp, vp, pt, lengths, scales = _edge_rows(RNG, 8, 128, 8, 4, [17],
                                             int8)
    q = jnp.zeros((2, 40, 128), jnp.bfloat16)
    arenas = {kp.shape} | {s.shape for s in scales.values()}

    def transposed(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "transpose" and any(
                    getattr(v.aval, "shape", None) in arenas
                    for v in eqn.invars):
                yield eqn
            for param in eqn.params.values():
                for sub in param if isinstance(param, (list, tuple)) \
                        else [param]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from transposed(sub)

    jaxpr = jax.make_jaxpr(
        lambda *a: ops.paged_decode_attention(*a, **scales))(
            q, kp, vp, pt, lengths).jaxpr
    assert "paged_decode_attention" in str(jaxpr)
    assert not list(transposed(jaxpr))


def test_paged_decode_scales_require_pair():
    kp, vp, pt = _page_arena(RNG, 1, 2, 64, 16, 2, 3)
    from repro.models.quant import quantize_rows
    kq, ks = quantize_rows(kp)
    q = jax.random.normal(RNG, (1, 4, 64))
    with pytest.raises(ValueError, match="both k_scales and v_scales"):
        ops.paged_decode_attention(q, kq, vp, pt, 8, k_scales=ks)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 64), (2, 7, 96), (130, 128), (1, 256)])
def test_rmsnorm_sweep(shape, dtype):
    x = jax.random.normal(RNG, shape, dtype)
    s = (jax.random.normal(RNG, (shape[-1],)) * 0.1 + 1.0).astype(dtype)
    out = rmsnorm(x, s, block_rows=8)
    want = ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# ssd scan (mamba2 / linear recurrence)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,S,dh,ds,Q", [
    (1, 2, 64, 32, 16, 16),
    (2, 4, 128, 64, 64, 32),
    (1, 1, 256, 128, 64, 128),
])
def test_ssd_scan_sweep(B, H, S, dh, ds, Q):
    ks = jax.random.split(RNG, 4)
    xb = jax.random.normal(ks[0], (B, H, S, dh))
    Bm = jax.random.normal(ks[1], (B, S, ds)) * 0.3
    Cm = jax.random.normal(ks[2], (B, S, ds)) * 0.3
    ld = -jnp.abs(jax.random.normal(ks[3], (B, H, S))) * 0.1
    y, h = ssd_scan(xb, Bm, Cm, ld, chunk=Q)
    yr, hr = ref.ssd_scan_ref(jnp.moveaxis(xb, 1, 2), Bm, Cm,
                              jnp.moveaxis(ld, 1, 2))
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.moveaxis(yr, 1, 2)),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=2e-4, rtol=2e-4)


@given(Q=st.sampled_from([8, 16, 32, 64]))
@settings(max_examples=4, deadline=None)
def test_ssd_chunk_invariance(Q):
    """The recurrence result must not depend on the chunk size."""
    ks = jax.random.split(RNG, 4)
    xb = jax.random.normal(ks[0], (1, 2, 64, 16))
    Bm = jax.random.normal(ks[1], (1, 64, 8)) * 0.3
    Cm = jax.random.normal(ks[2], (1, 64, 8)) * 0.3
    ld = -jnp.abs(jax.random.normal(ks[3], (1, 2, 64))) * 0.1
    y, h = ssd_scan(xb, Bm, Cm, ld, chunk=Q)
    y1, h1 = ssd_scan(xb, Bm, Cm, ld, chunk=64)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y1), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h1), atol=1e-4)


# ---------------------------------------------------------------------------
# end-to-end integration: cfg.attn_impl='pallas' serving path
# ---------------------------------------------------------------------------

def test_model_with_pallas_attention_matches_xla():
    m_x = get_smoke_model("qwen3-14b", n_layers=2, head_dim=32)
    m_p = get_smoke_model("qwen3-14b", n_layers=2, head_dim=32,
                          attn_impl="pallas")
    p = m_x.init_params(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              m_x.cfg.vocab_size)
    # prefill (flash kernel) + decode (flash-decoding kernel)
    cx = m_x.make_cache(2, 24)
    cp = m_p.make_cache(2, 24)
    lx, cx = m_x.prefill(p, {"tokens": toks}, cx)
    lp, cp = m_p.prefill(p, {"tokens": toks}, cp)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lx), atol=2e-4)
    for pos in range(16, 20):
        t = jnp.zeros((2, 1), jnp.int32)
        lx, cx = m_x.decode_step(p, cx, {"tokens": t}, pos)
        lp, cp = m_p.decode_step(p, cp, {"tokens": t}, pos)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lx), atol=2e-4)


def test_model_paged_pallas_decode_matches_xla():
    """decode_step_paged with attn_impl='pallas' (the paged flash-decoding
    kernel, scalar-prefetched page table, inside jit + layer scan) must
    match the XLA gather path."""
    m_x = get_smoke_model("qwen3-14b", n_layers=2, head_dim=32)
    m_p = get_smoke_model("qwen3-14b", n_layers=2, head_dim=32,
                          attn_impl="pallas")
    p = m_x.init_params(jax.random.PRNGKey(0))
    B, S, ps, NB = 2, 16, 8, 3
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              m_x.cfg.vocab_size)
    cache = m_x.make_cache(B, NB * ps)
    logits, cache = m_x.prefill(p, {"tokens": toks}, cache)
    # scatter the prefilled dense cache into per-sequence pages (1..B*NB)
    pt = (np.arange(B * NB) + 1).reshape(B, NB).astype(np.int32)

    def scatter(arena, dense):
        arena = np.array(arena)
        dense = np.asarray(dense)
        L = dense.shape[0]
        blk = dense.reshape((L, B, NB, ps) + dense.shape[3:])
        for b in range(B):
            for j in range(NB):
                arena[:, pt[b, j]] = blk[:, b, j]
        return jnp.asarray(arena)

    ax = jax.tree.map(scatter, m_x.make_paged_cache(1 + B * NB, ps), cache)
    ap = jax.tree.map(lambda t: t, ax)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    posv = jnp.full((B,), S, jnp.int32)
    for _ in range(3):
        lx, ax = m_x.decode_step_paged(p, ax, {"tokens": tok}, posv, pt, ps)
        lp, ap = m_p.decode_step_paged(p, ap, {"tokens": tok}, posv, pt, ps)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lx), atol=2e-4)
        tok = jnp.argmax(lx, axis=-1).astype(jnp.int32)[:, None]
        posv = posv + 1


def test_ops_fallback_on_odd_shapes():
    """Non-2^k sequence lengths fall back to a correct path."""
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, 2, 96, 64))
    k = jax.random.normal(ks[1], (1, 2, 96, 64))
    v = jax.random.normal(ks[2], (1, 2, 96, 64))
    out = ops.flash_attention(q, k, v)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("S", [130, 200])
def test_ops_flash_unaligned_length(S):
    """Lengths with no power-of-two tile run on a legal block (a multiple
    of 8 that divides S, else the whole axis) and match the oracle."""
    ks = jax.random.split(jax.random.fold_in(RNG, S), 3)
    q = jax.random.normal(ks[0], (1, 9, S, 64))
    k = jax.random.normal(ks[1], (1, 3, S, 64))
    v = jax.random.normal(ks[2], (1, 3, S, 64))
    out = ops.flash_attention(q, k, v)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_pick_block_is_always_legal():
    for S in range(1, 600):
        for want in (128, 256):
            b = ops._pick_block(S, want)
            assert S % b == 0
            assert b == S or (b % 8 == 0 and b <= want), (S, want, b)
