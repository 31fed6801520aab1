"""Fig. 21 (repro extension): copy-on-write prefix KV reuse — TTFT and
arena-resident bytes with vs without template-baked prompt caches.

TIDAL's templates carry warm state; PR 4 extends that state to the
function's shared prompt PREFIX: its KV is baked once into pinned pages of
the paged arena and every invocation whose prompt starts with it aliases
those pages (refcount++, copy-on-write for the trailing partial page) and
prefills only the suffix.  The analytic rows bound the win — suffix-only
prefill scales TTFT's execution slice by the uncached fraction — and
``--measured`` serves a shared-system-prompt workload through the LIVE
runtime twice (reuse on / off) on a smoke model, reporting wall-clock warm
TTFT, fresh pages mapped per request and the arena bytes the workload
makes resident.  Exits non-zero if reuse fails to beat full prefill on
either axis (the CI bench-smoke gate).
"""

import sys

from benchmarks.common import PAPER_HW, emit, write_bench_json
from repro.core import costmodel as cm
from repro.core.plans import plan_for

FULL_LEN = 2048                  # paper-style input length
PREFIX_FRACTIONS = (0.25, 0.5, 0.75, 0.9)


def analytic_rows():
    rows = []
    for arch in ("llama3-8b", "llama2-13b"):
        full = cm.ttft_execution(plan_for(arch, 1, FULL_LEN), PAPER_HW).total
        rows.append((f"{arch}/warm_full_prefill", round(full * 1e3, 1),
                     f"input={FULL_LEN}"))
        for frac in PREFIX_FRACTIONS:
            suffix = max(1, int(FULL_LEN * (1 - frac)))
            t = cm.ttft_execution(plan_for(arch, 1, suffix), PAPER_HW).total
            rows.append((f"{arch}/warm_reuse_{int(frac*100)}pct_prefix",
                         round(t * 1e3, 1), f"vs_full={full/t:.2f}x"))
    return rows


def measured_rows(arch: str = "llama3-8b", n_layers: int = 4,
                  prefix_len: int = 224, suffix_len: int = 8,
                  max_new: int = 4, n_requests: int = 4, reps: int = 4):
    """Serve the same shared-prefix workload with and without a baked
    template prompt and compare the live runtime's numbers."""
    import jax
    import numpy as np

    from repro.core import api as tidal
    from repro.models.registry import get_smoke_model
    from repro.runtime.faas import FaaSRuntime

    m = get_smoke_model(arch, n_layers=n_layers)
    params = m.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, m.cfg.vocab_size, prefix_len).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(
        0, m.cfg.vocab_size, suffix_len).astype(np.int32)])
        for _ in range(n_requests)]
    max_len = prefix_len + suffix_len + max_new

    def serve(template_prompt):
        rt = FaaSRuntime(n_slots=2, max_len=max_len, trace_seq=8,
                         page_size=8)
        rt.deploy(tidal.static_function("fn", m, params), {}, prewarm_seq=8,
                  template_prompt=template_prompt)
        rt.submit("fn", {}, prompts[0], max_new)        # cold: compile+fork
        pool = next(iter(rt._pools.values()))
        fresh0 = pool.stats["fresh_pages_mapped"]
        pool.peak_used_pages = pool.n_used_pages        # workload baseline
        outs = rt.submit_many([("fn", {}, p, max_new) for p in prompts])
        fresh = pool.stats["fresh_pages_mapped"] - fresh0
        ttft = min(o.ttft_s for o in outs)              # warm min over batch
        for _ in range(reps - 1):
            o = rt.submit("fn", {}, prompts[0], max_new)
            ttft = min(ttft, o.ttft_s)
        tokens = [o.tokens for o in outs]
        return ttft, fresh, pool.peak_used_pages * pool.page_nbytes(), tokens

    t_off, fresh_off, bytes_off, toks_off = serve(None)
    t_on, fresh_on, bytes_on, toks_on = serve(prefix)
    parity = all(np.array_equal(a, b) for a, b in zip(toks_off, toks_on))

    rows = [
        ("live/warm_ttft_full_prefill_ms", round(t_off * 1e3, 2),
         f"prompt={prefix_len + suffix_len}tok"),
        ("live/warm_ttft_prefix_reuse_ms", round(t_on * 1e3, 2),
         f"speedup={t_off / t_on:.2f}x suffix={suffix_len}tok"),
        ("live/fresh_pages_full_prefill", fresh_off,
         f"{n_requests}_requests"),
        ("live/fresh_pages_prefix_reuse", fresh_on,
         f"saving={fresh_off - fresh_on}_pages"),
        ("live/resident_bytes_full_prefill", bytes_off, "workload_peak"),
        ("live/resident_bytes_prefix_reuse", bytes_on,
         f"saving={1 - bytes_on / bytes_off:.0%}"),
        ("live/token_parity", "ok" if parity else "MISMATCH",
         f"{n_requests}_shared_prefix_requests"),
    ]
    if not parity:
        raise SystemExit("prefix reuse changed tokens")
    if t_on >= t_off:
        raise SystemExit("prefix reuse must lower warm TTFT")
    if fresh_on >= fresh_off or bytes_on >= bytes_off:
        raise SystemExit("prefix reuse must map fewer fresh pages/bytes")
    return rows


def main(measured: bool = False):
    rows = analytic_rows()
    if measured:
        mrows = measured_rows()     # raises before returning on gate failure
        rows += mrows
        write_bench_json("fig21_prefix_reuse", {n: v for n, v, _ in mrows},
                         gates={"token_parity": True,
                                "reuse_lowers_warm_ttft": True,
                                "reuse_maps_fewer_fresh_pages": True})
    return emit(rows, header=("name", "value", "derived"))


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main(measured="--measured" in sys.argv)
