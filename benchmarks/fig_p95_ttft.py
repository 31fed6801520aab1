"""p95 TTFT under open-loop load: interleaved async gateway vs
drain-to-completion invocation (the paper's headline tail metric — TIDAL
reports a 76.0% improvement in 95%-ile time-to-first-token).

Default (analytic): replays one Poisson two-function trace through both
scheduling disciplines with cost-model service times — drain runs each
request's full decode before the next request starts; interleaved admits
on arrival and hands out bounded token quanta round-robin — and reports
p50/p95 TTFT for each.

``--measured``: drives the LIVE serving runtime on CPU smoke models
through the real ``InvocationGateway``, replaying the identical arrival
schedule in both modes, and GATES on

  * interleaved p95 TTFT strictly below drain-to-completion p95, and
  * every streamed token sequence bit-identical to the synchronous
    sequential engine at temperature 0 (in both modes).
"""

import sys
import time

import numpy as np

from benchmarks.common import PAPER_HW, emit, write_bench_json
from repro.core import costmodel as cm
from repro.core.plans import plan_for

ARCH = "llama3-8b"                 # analytic service times
QUANTUM = 2                        # decode steps per gateway quantum


# ---------------------------------------------------------------------------
# analytic: one trace, two disciplines
# ---------------------------------------------------------------------------

def _trace(rng, t_long, n_short=12, n_long=4):
    """Poisson short arrivals riding over regularly spaced long requests.
    Times are in units of one long request's service time ``t_long``."""
    longs = [(i * 0.9 * t_long, "long") for i in range(n_long)]
    shorts, t = [], 0.0
    for _ in range(n_short):
        t += rng.exponential(0.25 * t_long)
        shorts.append((t, "short"))
    return sorted(longs + shorts)


def _simulate(trace, prefill_s, step_s, n_tokens, interleave):
    """Single-server token-granular replay.  Drain: FIFO, each request
    decodes to completion.  Interleaved: every in-flight request gets
    QUANTUM decode steps per rotation (prefill still serializes — it is
    one batch-1 call either way)."""
    clock, ttfts = 0.0, {}
    if not interleave:
        for t, kind in trace:
            clock = max(clock, t) + prefill_s
            ttfts.setdefault(kind, []).append(clock - t)
            clock += (n_tokens[kind] - 1) * step_s
        return ttfts
    pending = list(trace)
    active = []                              # [kind, tokens_left]
    while pending or active:
        if not active:
            clock = max(clock, pending[0][0])
        while pending and pending[0][0] <= clock:
            t, kind = pending.pop(0)
            clock += prefill_s               # prefill-on-arrival
            ttfts.setdefault(kind, []).append(clock - t)
            active.append([kind, n_tokens[kind] - 1])
        for entry in list(active):
            burst = min(QUANTUM, entry[1])
            clock += burst * step_s
            entry[1] -= burst
            if entry[1] <= 0:
                active.remove(entry)
    return ttfts


def analytic_rows():
    plan_prefill = plan_for(ARCH, 1, 2048)
    plan_step = plan_for(ARCH, 1, 1)
    prefill_s = cm.ttft_execution(plan_prefill, PAPER_HW).total
    step_s = cm.ttft_execution(plan_step, PAPER_HW).total
    n_tokens = {"long": 256, "short": 16}
    t_long = prefill_s + n_tokens["long"] * step_s
    trace = _trace(np.random.default_rng(0), t_long)
    rows = []
    p95 = {}
    for name, interleave in (("drain", False), ("interleaved", True)):
        ttfts = _simulate(trace, prefill_s, step_s, n_tokens, interleave)
        allt = sorted(ttfts["long"] + ttfts["short"])
        p95[name] = float(np.percentile(allt, 95))
        rows += [
            (f"{ARCH}/{name}/p50_ttft",
             round(float(np.percentile(allt, 50)) * 1e3, 1), ""),
            (f"{ARCH}/{name}/p95_ttft", round(p95[name] * 1e3, 1), ""),
            (f"{ARCH}/{name}/p95_short_ttft",
             round(float(np.percentile(ttfts["short"], 95)) * 1e3, 1), ""),
        ]
    rows.append(("p95_improvement",
                 round((1 - p95["interleaved"] / p95["drain"]) * 100, 1),
                 "percent, paper=76.0 (Fig. 13 tail)"))
    return rows


# ---------------------------------------------------------------------------
# measured: the live gateway, both modes, identical arrivals
# ---------------------------------------------------------------------------

def _run_mode(rt, arrivals, interleave):
    """Replay ``arrivals`` (offset_s, fn, prompt, max_new) open-loop
    through the runtime's gateway in the given mode."""
    from repro.runtime.gateway import InvocationRequest

    rt.gateway.interleave = interleave
    handles = rt.gateway.replay(
        [(due, InvocationRequest(fn, prompt, max_new_tokens=max_new))
         for due, fn, prompt, max_new in arrivals])
    return [h.result() for h in handles]


def measured_rows():
    import jax

    from repro.core import api as tidal
    from repro.models.registry import get_smoke_model
    from repro.runtime.engine import Engine
    from repro.runtime.faas import FaaSRuntime

    max_len, page, prompt_len = 48, 8, 8
    n_long, n_short_tok = 24, 4
    models = {fn: get_smoke_model("smollm-135m", n_layers=2)
              for fn in ("fn-long", "fn-short")}   # distinct arenas
    params = {fn: m.init_params(jax.random.PRNGKey(i))
              for i, (fn, m) in enumerate(models.items())}
    rt = FaaSRuntime(n_slots=2, max_len=max_len, page_size=page,
                     trace_seq=prompt_len, gateway_quantum=QUANTUM)
    for fn, m in models.items():
        rt.deploy(tidal.static_function(fn, m, params[fn]), {},
                  prewarm_seq=prompt_len)

    rng = np.random.default_rng(0)
    prompts = {fn: rng.integers(0, models[fn].cfg.vocab_size,
                                prompt_len).astype(np.int32)
               for fn in models}
    # sequential reference tokens (the synchronous path, temperature 0)
    want = {}
    for fn, m in models.items():
        n = n_long if fn == "fn-long" else n_short_tok
        want[fn] = Engine(m, params[fn], donate_cache=False).generate(
            prompts[fn][None], max_new_tokens=n, cache_len=max_len).tokens[0]

    # calibrate: one warm long request bounds the congestion window
    rt.submit("fn-long", {}, prompts["fn-long"], n_long)
    t_cal = time.perf_counter()
    rt.submit("fn-long", {}, prompts["fn-long"], n_long)
    t_long = time.perf_counter() - t_cal
    rt.submit("fn-short", {}, prompts["fn-short"], n_short_tok)

    # open-loop mix: two long decodes with Poisson shorts riding on top
    arrivals = [(0.0, "fn-long", prompts["fn-long"], n_long),
                (0.55 * t_long, "fn-long", prompts["fn-long"], n_long)]
    t = 0.0
    for _ in range(6):
        t += float(rng.exponential(0.18 * t_long))
        arrivals.append((t, "fn-short", prompts["fn-short"], n_short_tok))
    arrivals.sort(key=lambda a: a[0])

    rows, p95 = [], {}
    for name, interleave in (("drain", False), ("interleaved", True)):
        results = _run_mode(rt, arrivals, interleave)
        for res in results:                      # token parity, both modes
            np.testing.assert_array_equal(res.tokens, want[res.fn_name])
        ttfts = sorted(r.ttft_s for r in results)
        p95[name] = float(np.percentile(ttfts, 95))
        rows += [
            (f"measured/{name}/p50_ttft",
             round(float(np.percentile(ttfts, 50)) * 1e3, 1), "wall-clock"),
            (f"measured/{name}/p95_ttft", round(p95[name] * 1e3, 1),
             "wall-clock"),
        ]
    assert p95["interleaved"] < p95["drain"], (
        f"interleaved gateway p95 TTFT {p95['interleaved']*1e3:.1f}ms is "
        f"not below drain-to-completion {p95['drain']*1e3:.1f}ms")
    rows.append(("measured/p95_improvement",
                 round((1 - p95["interleaved"] / p95["drain"]) * 100, 1),
                 "percent, gate: > 0"))
    return rows


def main(measured: bool = False):
    rows = analytic_rows()
    if measured:
        mrows = measured_rows()     # raises before returning on gate failure
        rows += mrows
        write_bench_json("fig_p95_ttft", {n: v for n, v, _ in mrows},
                         gates={"interleaved_p95_below_drain": True,
                                "token_parity": True})
    return emit(rows)


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main(measured="--measured" in sys.argv)
