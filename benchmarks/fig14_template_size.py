"""Fig. 14: TTFT vs template size (0G -> whole model), llama family +
LoRA variants.  Paper: Tidal-Warm is 14%~48% faster than Tidal-0G; dynamic
functions need SMALLER templates to reach best TTFT (their adapter init
overlaps more loading).

``--paged`` appends a LIVE paged-vs-dense resident-state comparison on a
smoke-scale model (CPU): the same mixed-length workload served at the same
concurrency through the dense slot pool and the block-paged pool, reporting
resident KV bytes, the max concurrency each layout affords at the dense
pool's HBM budget, and greedy token parity between the two paths.

``--kv-dtype int8 --measured`` appends the quantized-arena comparison: the
same workload served at matched concurrency through an fp paged arena and
an int8 one (per-row scales, dequantized INSIDE the Pallas decode kernel —
the XLA oracle is monkeypatched to raise, so a silent fallback fails the
run).  Gates: >= 1.8x lower resident KV bytes, exact first generated token
per request, and bounded greedy divergence over the full completions."""

import sys

from benchmarks.common import PAPER_HW, emit, lora_bytes, write_bench_json
from repro.core import costmodel as cm
from repro.core.plans import plan_for


def paged_rows(arch: str = "llama3-8b", n_layers: int = 2,
               n_slots: int = 4, max_len: int = 64, page_size: int = 8):
    """Serve one mixed-length batch through both pool layouts and compare
    footprints at equal concurrency (and concurrency at equal footprint)."""
    import jax
    import numpy as np

    from repro.models.registry import get_smoke_model
    from repro.runtime.continuous import ContinuousBatchingEngine
    from repro.runtime.kv_pool import KVCachePool

    m = get_smoke_model(arch, n_layers=n_layers)
    params = m.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # mixed-length workload: short chats to near-max_len completions
    reqs = [(rng.integers(0, m.cfg.vocab_size, s).astype(np.int32), n)
            for s, n in [(6, 4), (40, 8), (12, 6), (50, 8)]]
    blocks = sum(-(-(len(p) + n) // page_size) for p, n in reqs)
    n_pages = 1 + blocks                     # sized to demand, + null page

    dense_eng = ContinuousBatchingEngine(m, params, n_slots=n_slots,
                                         max_len=max_len, paged=False)
    paged_eng = ContinuousBatchingEngine(m, params, n_slots=n_slots,
                                         max_len=max_len,
                                         page_size=page_size,
                                         n_pages=n_pages)
    outs = []
    for eng in (dense_eng, paged_eng):
        rids = [eng.submit(p, n) for p, n in reqs]
        res = eng.run()
        outs.append([res[r].tokens for r in rids])
    parity = all(np.array_equal(a, b) for a, b in zip(*outs))

    dense_bytes = dense_eng.pool.nbytes()
    paged_bytes = paged_eng.pool.nbytes()
    assert isinstance(dense_eng.pool, KVCachePool)
    # concurrency each layout affords inside the DENSE pool's HBM budget,
    # for requests of this workload's mean footprint
    page_bytes = paged_bytes / n_pages
    mean_blocks = blocks / len(reqs)
    conc_paged = int((dense_bytes // page_bytes - 1) // mean_blocks)
    rows = [
        ("paged/dense_resident_kv_bytes", dense_bytes,
         f"slots={n_slots}x{max_len}tok"),
        ("paged/paged_resident_kv_bytes", paged_bytes,
         f"pages={n_pages}x{page_size}tok saving={dense_bytes/paged_bytes:.2f}x"),
        ("paged/max_concurrency_equal_hbm_dense", n_slots,
         f"budget={dense_bytes}B"),
        ("paged/max_concurrency_equal_hbm_paged", conc_paged,
         f"{conc_paged / n_slots:.1f}x_dense"),
        ("paged/greedy_token_parity", "ok" if parity else "MISMATCH",
         f"{len(reqs)}_mixed_len_requests"),
    ]
    if not parity:
        raise SystemExit("paged/dense token mismatch")
    if paged_bytes >= dense_bytes:
        raise SystemExit("paged pool must be strictly smaller than dense")
    return rows


def int8_rows(arch: str = "llama3-8b", n_layers: int = 2,
              n_slots: int = 4, max_len: int = 64, page_size: int = 8,
              max_divergence: float = 0.25):
    """Serve one mixed-length batch through an fp and an int8 paged arena.

    Both engines run the Pallas paged-decode kernel (``attn_impl='pallas'``)
    at the same slot/page capacity; the int8 engine's decode is proven to
    stay on the in-kernel dequant path by monkeypatching the XLA oracle to
    raise.  Gates: resident-bytes ratio >= 1.8x, first token exact per
    request (prefill is fp in both arenas), full-completion divergence
    <= ``max_divergence``.
    """
    import jax
    import numpy as np

    from repro.kernels import ref
    from repro.models.registry import get_smoke_model
    from repro.runtime.continuous import ContinuousBatchingEngine

    rng = np.random.default_rng(0)
    vocab = get_smoke_model(arch, n_layers=n_layers).cfg.vocab_size
    reqs = [(rng.integers(0, vocab, s).astype(np.int32), n)
            for s, n in [(6, 4), (40, 8), (12, 6), (50, 8)]]
    blocks = sum(-(-(len(p) + n) // page_size) for p, n in reqs)
    n_pages = 1 + blocks

    def serve(kv_dtype, guard_no_fallback=False):
        m = get_smoke_model(arch, n_layers=n_layers, attn_impl="pallas")
        params = m.init_params(jax.random.PRNGKey(0))
        eng = ContinuousBatchingEngine(m, params, n_slots=n_slots,
                                       max_len=max_len,
                                       page_size=page_size,
                                       n_pages=n_pages, kv_dtype=kv_dtype)
        rids = [eng.submit(p, n) for p, n in reqs]
        if guard_no_fallback:
            orig = ref.paged_decode_attention_ref

            def boom(*a, **k):
                raise AssertionError(
                    "paged decode fell back to the XLA oracle")
            ref.paged_decode_attention_ref = boom
            try:
                res = eng.run()
            finally:
                ref.paged_decode_attention_ref = orig
        else:
            res = eng.run()
        return eng, [np.asarray(res[r].tokens) for r in rids]

    fp_eng, fp_toks = serve(None)
    q_eng, q_toks = serve("int8", guard_no_fallback=True)

    fp_res = fp_eng.pool.peak_used_pages * fp_eng.pool.page_nbytes()
    q_res = q_eng.pool.peak_used_pages * q_eng.pool.page_nbytes()
    ratio = fp_res / q_res
    first_ok = all(a[0] == b[0] for a, b in zip(fp_toks, q_toks))
    total = sum(len(a) for a in fp_toks)
    diff = sum(int(np.sum(a != b)) for a, b in zip(fp_toks, q_toks))
    divergence = diff / total
    rows = [
        ("int8/fp_resident_kv_bytes", fp_res,
         f"peak_pages={fp_eng.pool.peak_used_pages}"),
        ("int8/int8_resident_kv_bytes", q_res,
         f"saving={ratio:.2f}x (gate>=1.8x)"),
        ("int8/first_token_exact", "ok" if first_ok else "MISMATCH",
         f"{len(reqs)}_requests"),
        ("int8/greedy_divergence", round(divergence, 4),
         f"{diff}/{total}_tokens (gate<={max_divergence})"),
        ("int8/pallas_dequant_no_fallback", "ok",
         "xla_oracle_monkeypatched"),
    ]
    if ratio < 1.8:
        raise SystemExit(
            f"int8 arena saves only {ratio:.2f}x resident bytes (< 1.8x)")
    if not first_ok:
        raise SystemExit("int8 arena diverged on a FIRST token (prefill "
                         "is fp — the first sample must match exactly)")
    if divergence > max_divergence:
        raise SystemExit(
            f"int8 greedy divergence {divergence:.3f} > {max_divergence}")
    return rows


def main(paged: bool = False, kv_int8: bool = False):
    rows = []
    for arch in ("llama3-8b", "llama2-13b"):
        plan = plan_for(arch, 1, 2048)
        for lora in (False, True):
            dyn = lora_bytes(plan) if lora else 0
            tag = arch + ("-lora" if lora else "")
            base = None
            best_g = None
            for g in (0, 2, 4, 6, 8, 12, 16, 32):
                tb = min(g << 30, plan.total_weight_bytes)
                t = cm.ttft_tidal(plan, PAPER_HW, template_bytes=tb,
                                  dynamic_bytes=dyn).total
                if base is None:
                    base = t
                if best_g is None and g and abs(
                        t - cm.ttft_tidal(plan, PAPER_HW,
                                          template_bytes=plan.total_weight_bytes,
                                          dynamic_bytes=dyn).total) < 1e-3:
                    best_g = g
                rows.append((f"{tag}/template_{g}G", round(t * 1e3, 1),
                             f"vs_0G={base/t:.2f}x"))
            rows.append((f"{tag}/saturation_point",
                         best_g if best_g is not None else "warm",
                         "GiB_to_reach_warm_ttft"))
    if paged:
        rows += paged_rows()
    if kv_int8:
        irows = int8_rows()
        rows += irows
        write_bench_json(          # int8_rows raises before this on failure
            "fig14_template_size", {n: v for n, v, _ in irows},
            gates={"int8_resident_bytes_ratio_ge_1p8": True,
                   "first_token_exact": True,
                   "greedy_divergence_bounded": True})
    return emit(rows, header=("name", "value", "derived"))


def _cli_kv_int8(argv) -> bool:
    if "--kv-dtype" not in argv:
        return False
    val = argv[argv.index("--kv-dtype") + 1:][:1]
    if val != ["int8"]:
        raise SystemExit(f"--kv-dtype supports only 'int8' (got {val})")
    return True


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main(paged="--paged" in sys.argv, kv_int8=_cli_kv_int8(sys.argv))
