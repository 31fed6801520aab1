"""Predictive prewarm control plane vs reactive keep-alive decay on one
bursty multi-function trace.

Default (analytic): builds a periodic two-function burst trace, proves
the JSONL trace round-trip is bit-identical, and sweeps ``ClusterSim``
keep-alive windows over the imported trace — the offline policy search
whose winning window the live control plane has to discover ONLINE from
the arrival stream (cold starts vanish once the window covers the burst
period, at the cost of held HBM).

``--measured``: replays the identical recorded trace twice through the
LIVE gateway on CPU smoke models — once under pure keep-alive decay,
once with a :class:`~repro.runtime.controlplane.ControlPlane` attached
(arrival forecasting + runtime-learned prefix cache) — and GATES on

  * strictly lower steady-state cold-start fraction with the control
    plane (training bursts excluded from the measured window),
  * strictly lower steady-state p95 TTFT,
  * per-request token parity with the sequential engine in BOTH modes,
  * runtime-learned (non-template) prefix reuse hits > 0 with pinned
    bytes within the control plane's budget, and
  * the exported/imported trace replaying bit-for-bit.
"""

import os
import sys
import tempfile
import time

import numpy as np

from benchmarks.common import emit, write_bench_json
from repro.core.plans import plan_for
from repro.core.scheduler import (ClusterSim, FunctionProfile,
                                  SchedulerConfig, SimRequest, export_trace,
                                  import_trace, summarize)

PAGE = 8
PREFIX_PAGES = 2                    # each function's hot 2-page prompt root
BURST = 4                           # requests per burst per function
TRAIN_BURSTS = 3                    # forecaster/observer warm-up window
MEAS_BURSTS = 5                     # steady-state gated window


def _bursty_trace(period_s, input_len, n_bursts, intra_gap_s,
                  fns=("fn-a", "fn-b")) -> list:
    """Two functions bursting at the same period, half a period apart."""
    reqs, rid = [], 0
    for k, fn in enumerate(fns):
        phase = k * period_s / 2.0
        for i in range(n_bursts):
            for j in range(BURST):
                reqs.append(SimRequest(fn, phase + i * period_s
                                       + j * intra_gap_s, input_len, rid))
                rid += 1
    reqs.sort(key=lambda r: (r.arrival_s, r.req_id))
    return reqs


def _roundtrip(trace) -> list:
    """Export -> import, asserting the bit-identical round-trip."""
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "trace.jsonl")
        export_trace(trace, path)
        back = import_trace(path)
    assert back == trace, "trace JSONL round-trip is not bit-identical"
    return back


# ---------------------------------------------------------------------------
# analytic: offline keep-alive policy search over the recorded trace
# ---------------------------------------------------------------------------

def analytic_rows(period_s: float = 30.0):
    trace = _roundtrip(_bursty_trace(period_s, input_len=1154, n_bursts=8,
                                     intra_gap_s=0.05))
    plan = plan_for("llama3-8b", 1, 1154)
    profs = {fn: FunctionProfile(fn, lambda L: plan_for("llama3-8b", 1, L),
                                 model_bytes=plan.total_weight_bytes)
             for fn in ("fn-a", "fn-b")}
    rows = [("sim/trace_roundtrip", "ok", f"{len(trace)}_requests_jsonl")]
    frac = {}
    for ka in (5.0, 15.0, 45.0):
        res = summarize(ClusterSim(
            SchedulerConfig(n_gpus=2, keep_alive_s=ka), profs).run(trace))
        frac[ka] = res["cold"] / res["n"]
        rows += [
            (f"sim/keepalive_{ka:g}s/cold_frac", round(frac[ka], 3),
             f"{res['cold']}/{res['n']}"),
            (f"sim/keepalive_{ka:g}s/p95_ttft",
             round(res["p95"] * 1e3, 1), ""),
        ]
    # the policy-search headline the online control plane must match:
    # a window covering the burst period eliminates recurring colds
    rows.append(("sim/cold_frac_drop",
                 round(frac[5.0] - frac[45.0], 3),
                 "window_covers_period_vs_decay"))
    assert frac[45.0] < frac[5.0], (
        "covering keep-alive must beat decay on a periodic trace")
    return rows


# ---------------------------------------------------------------------------
# measured: the live gateway, reactive decay vs control plane
# ---------------------------------------------------------------------------

def _build_runtime(models, params, keep_alive_s):
    from repro.core import api as tidal
    from repro.runtime.faas import FaaSRuntime

    rt = FaaSRuntime(n_slots=2, max_len=48, page_size=PAGE,
                     trace_seq=PREFIX_PAGES * PAGE,
                     keep_alive_s=keep_alive_s)
    for fn, m in models.items():
        rt.deploy(tidal.static_function(fn, m, params[fn]), {},
                  prewarm_seq=PREFIX_PAGES * PAGE)
    return rt


def _warm_compiles(rt, prompts, max_new):
    """Pay every lazy compile once, then evict back to a cold runtime."""
    for fn, plist in prompts.items():
        rt.submit(fn, {}, plist[0], max_new)
    rt.evict()
    rt.fn_stats.clear()


def measured_rows():
    import jax

    from repro.models.registry import get_smoke_model
    from repro.runtime.controlplane import ControlPlane, trace_schedule
    from repro.runtime.engine import Engine

    max_new, prompt_len = 4, (PREFIX_PAGES + 1) * PAGE
    models = {fn: get_smoke_model("smollm-135m", n_layers=2)
              for fn in ("fn-a", "fn-b")}      # distinct arenas per fn
    params = {fn: m.init_params(jax.random.PRNGKey(i))
              for i, (fn, m) in enumerate(models.items())}

    # per-function prompts: one hot 2-page root, every suffix UNIQUE —
    # only runtime observation (never a deploy-time template) can turn
    # the root into reuse
    rng = np.random.default_rng(0)
    n_bursts = TRAIN_BURSTS + MEAS_BURSTS
    roots, prompts = {}, {}
    for fn, m in models.items():
        roots[fn] = rng.integers(0, m.cfg.vocab_size,
                                 PREFIX_PAGES * PAGE).astype(np.int32)
        prompts[fn] = [np.concatenate([roots[fn], rng.integers(
            0, m.cfg.vocab_size, prompt_len - len(roots[fn]))]
        ).astype(np.int32) for _ in range(n_bursts * BURST)]

    # calibrate the burst period off the real fork cost
    cal = _build_runtime(models, params, keep_alive_s=1e9)
    _warm_compiles(cal, prompts, max_new)
    t0 = time.perf_counter()
    cal.submit("fn-a", {}, prompts["fn-a"][0], max_new)
    t_fork = time.perf_counter() - t0
    period = max(6.0 * t_fork, 0.4)
    keep_alive = period / 4.0               # decays before the next burst

    trace = _roundtrip(_bursty_trace(period, prompt_len, n_bursts,
                                     intra_gap_s=period / 50.0))
    counters = {fn: 0 for fn in models}

    def prompt_for(req):
        p = prompts[req.fn_name][counters[req.fn_name]]
        counters[req.fn_name] += 1
        return p

    schedule = trace_schedule(trace, prompt_for, max_new_tokens=max_new)

    # sequential greedy reference for every scheduled prompt
    want = {}
    for fn, m in models.items():
        eng = Engine(m, params[fn], donate_cache=False)
        for _, req in schedule:
            if req.fn_name == fn:
                want[id(req)] = eng.generate(
                    np.asarray(req.prompt)[None], max_new_tokens=max_new,
                    cache_len=48).tokens[0]

    meas_start = TRAIN_BURSTS * period      # steady-state window opens
    rows, cold_frac, p95, cp = [], {}, {}, None
    for name in ("reactive", "predictive"):
        rt = _build_runtime(models, params, keep_alive)
        _warm_compiles(rt, prompts, max_new)
        if name == "predictive":
            cp = ControlPlane(rt, min_hits=3,
                              prewarm_horizon_s=period / 2.0,
                              prewarm_p=0.4,
                              tick_interval_s=min(0.02, period / 50.0))
        handles = rt.gateway.replay(schedule)
        results = [h.result() for h in handles]
        for (due, req), res in zip(schedule, results):
            np.testing.assert_array_equal(res.tokens, want[id(req)])
        steady = [(due, res) for (due, req), res
                  in zip(schedule, results) if due >= meas_start]
        colds = sum(1 for _, r in steady if r.kind in ("cold", "fork"))
        cold_frac[name] = colds / len(steady)
        p95[name] = float(np.percentile(
            sorted(r.ttft_s for _, r in steady), 95))
        if name == "predictive":
            reuse = sum(1 for _, r in steady if r.reused_prefix_len > 0)
            pinned = cp.pinned_nbytes()
            assert reuse > 0, "no runtime-learned prefix reuse hits"
            assert 0 < pinned <= cp.pinned_bytes_budget, (
                f"pinned {pinned}B outside (0, {cp.pinned_bytes_budget}]B")
            rows += [
                ("measured/predictive/reuse_hits", reuse,
                 f"of_{len(steady)}_steady_requests_learned_not_template"),
                ("measured/predictive/pinned_bytes", pinned,
                 f"budget={cp.pinned_bytes_budget}"),
                ("measured/predictive/prewarm_forks",
                 cp.stats["prewarm_forks"], ""),
                ("measured/predictive/prefix_bakes",
                 cp.stats["prefix_bakes"], ""),
            ]
        rows += [
            (f"measured/{name}/cold_frac", round(cold_frac[name], 3),
             f"steady_state_{len(steady)}_requests"),
            (f"measured/{name}/p95_ttft", round(p95[name] * 1e3, 1),
             "wall-clock"),
        ]
    assert cold_frac["predictive"] < cold_frac["reactive"], (
        f"predictive cold fraction {cold_frac['predictive']:.3f} not below "
        f"reactive {cold_frac['reactive']:.3f}")
    assert p95["predictive"] < p95["reactive"], (
        f"predictive p95 TTFT {p95['predictive']*1e3:.1f}ms not below "
        f"reactive {p95['reactive']*1e3:.1f}ms")
    rows += [
        ("measured/cold_frac_drop",
         round(cold_frac["reactive"] - cold_frac["predictive"], 3),
         "gate: > 0"),
        ("measured/p95_improvement",
         round((1 - p95["predictive"] / p95["reactive"]) * 100, 1),
         "percent, gate: > 0"),
    ]
    write_bench_json(
        "fig_predictive_prewarm",
        {n: v for n, v, _ in rows if n.startswith("measured/")},
        gates={"cold_frac_strictly_lower": True,
               "p95_ttft_strictly_lower": True,
               "token_parity_both_modes": True,
               "learned_prefix_reuse_hits": True,
               "pinned_bytes_within_budget": True,
               "trace_jsonl_roundtrip": True})
    return rows


def main(measured: bool = False):
    rows = analytic_rows()
    if measured:
        rows += measured_rows()
    return emit(rows)


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main(measured="--measured" in sys.argv)
