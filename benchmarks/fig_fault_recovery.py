"""Fault recovery: supervised retry vs giving up, under injected crashes.

Default (analytic): a ClusterSim arrival trace with seeded per-attempt
crashes.  A crashed attempt burns part of its service time and drops the
GPU's warm state; with retries the scheduler re-places the request on
the least-loaded online GPU after capped exponential backoff, without
them the request fails (TTFT = inf).  Reports completed-request
fraction, retry/failure counts and p95 TTFT for both disciplines.

``--measured``: drives the LIVE serving runtime on CPU smoke models —
two functions co-resident on ONE shared paged arena — replaying an
identical request batch under an identical deterministic
:class:`FaultPlan` (engine crashes at fixed step visits) with
supervision on (bounded retry) and off (max_retries=0), and GATES on

  * supervised completed fraction strictly above no-retry,
  * supervised p95 TTFT (failures count as +inf) strictly below
    no-retry, and finite,
  * at least one supervised request actually retried, and every
    completed request's greedy tokens bit-identical to its fault-free
    sequential-engine oracle (crash replays are invisible to consumers),
  * after EVERY injected crash: co-tenant partition stats bit-identical
    across the teardown and the arena's free-page gain exactly the dead
    partition's mapped pages (the lease retired cleanly),
  * the pool back at its pre-fault baseline after each run,

plus a weight-fetch scenario: a transient injected fetch fault is
absorbed by the streamer's retry (no engine failure at all), while a
persistent one kills the fork and supervision re-forks to a
bit-identical result.
"""

import sys
import time

import numpy as np

from benchmarks.common import emit, write_bench_json
from repro.core.plans import plan_for
from repro.core.scheduler import (ClusterSim, FunctionProfile,
                                  SchedulerConfig, make_trace, summarize)

SEED = 0
CRASH_RATE = 0.3
N_REQ = 12                         # measured: requests per run
MAX_NEW = 6


# ---------------------------------------------------------------------------
# analytic: cluster-level availability under seeded crashes
# ---------------------------------------------------------------------------

def analytic_rows():
    plan = plan_for("smollm-135m", 1, 867)
    profiles = {"f": FunctionProfile(
        name="f", plan_for_len=lambda L: plan_for("smollm-135m", 1, L),
        model_bytes=plan.total_weight_bytes)}
    trace = make_trace({"f": 2.0}, duration_s=20.0, fn_tasks={"f": "mail"},
                       seed=SEED)

    def run(max_retries):
        cfg = SchedulerConfig(n_gpus=2, policy="tidal", dk=True,
                              keep_alive_s=5.0, crash_rate=CRASH_RATE,
                              crash_seed=SEED, max_retries=max_retries)
        return summarize(ClusterSim(cfg, profiles).run(trace))

    retry, noretry = run(3), run(0)
    assert retry["completed_frac"] > noretry["completed_frac"], (
        f"retries did not improve completion: {retry['completed_frac']:.2f}"
        f" vs {noretry['completed_frac']:.2f}")
    rows = []
    for name, s in (("retry", retry), ("no_retry", noretry)):
        rows += [
            (f"analytic/{name}/completed_frac",
             round(s["completed_frac"], 3),
             f"crash_rate={CRASH_RATE}, gate: retry > no_retry"),
            (f"analytic/{name}/failed", s["failed"], "requests"),
            (f"analytic/{name}/retried", s["retried"],
             "requests that crashed >= once yet completed"),
            (f"analytic/{name}/p95_ttft", round(s["p95"] * 1e3, 1),
             "completed requests only"),
        ]
    return rows


# ---------------------------------------------------------------------------
# measured: the live runtime under a deterministic fault plan
# ---------------------------------------------------------------------------

def _build_runtime(m, params, fns, max_retries):
    from repro.core import api as tidal
    from repro.runtime.faas import FaaSRuntime

    rt = FaaSRuntime(n_slots=2, max_len=32, trace_seq=8, page_size=4,
                     prewarm=False, max_retries=max_retries,
                     retry_backoff_s=0.0)
    for fn in fns:
        rt.deploy(tidal.static_function(fn, m, params[fn]), {})
    return rt


def _crash_run(m, params, fns, prompts, want, max_retries):
    """One run: warm up, install a fresh copy of the SAME fault plan,
    replay the batch, and collect per-request outcomes + teardown logs."""
    from repro.runtime.errors import EngineFailure
    from repro.runtime.faults import FaultPlan, FaultSpec, use_fault_plan
    from repro.runtime.gateway import InvocationRequest

    rt = _build_runtime(m, params, fns, max_retries)
    for fn in fns:                       # compile + warm both engines
        rt.submit(fn, {}, prompts[0][1], 2)
    baseline = rt.kv_pool_stats()

    plan = FaultPlan([FaultSpec("engine_step", at=v) for v in (3, 7, 11)],
                     seed=SEED)
    outcomes = []
    t0 = time.perf_counter()
    with use_fault_plan(plan):
        handles = [rt.submit(InvocationRequest(fn, p, max_new_tokens=MAX_NEW))
                   for fn, p in prompts]
        for i, h in enumerate(handles):
            try:
                res = h.result()
                np.testing.assert_array_equal(res.tokens, want[i])
                outcomes.append(("ok", res.ttft_s, res.retries))
            except EngineFailure:
                outcomes.append(("failed", float("inf"), h.retries))
    wall = time.perf_counter() - t0

    for entry in rt.gateway.failures:    # partition-safe teardown, always
        assert entry["cotenants_intact"], f"co-tenant stats moved: {entry}"
        assert (entry["free_pages_after"] - entry["free_pages_before"]
                == entry["victim_mapped_pages"]), f"page leak: {entry}"
    assert rt.kv_pool_stats() == baseline, "arena not back at baseline"
    assert len(plan.fired) > 0, "the fault plan never fired"
    return outcomes, rt.gateway.stats, wall


def _crash_rows(m, params, fns, prompts, want):
    rows, frac, p95 = [], {}, {}
    for name, retries in (("supervised", 2), ("no_retry", 0)):
        outcomes, stats, wall = _crash_run(m, params, fns, prompts, want,
                                           retries)
        ttfts = sorted(t for _, t, _ in outcomes)
        frac[name] = sum(1 for s, _, _ in outcomes if s == "ok") / len(outcomes)
        # order statistic, not interpolation: +inf failures must yield an
        # infinite percentile, not NaN from inf - inf
        p95[name] = float(np.percentile(ttfts, 95, method="higher"))
        n_retried = sum(1 for s, _, r in outcomes if s == "ok" and r > 0)
        rows += [
            (f"measured/{name}/completed_frac", round(frac[name], 3),
             "engine crashes at step visits 3, 7, 11"),
            (f"measured/{name}/p95_ttft",
             round(p95[name] * 1e3, 1) if np.isfinite(p95[name]) else "inf",
             "failures count as +inf"),
            (f"measured/{name}/engine_failures", stats["engine_failures"],
             ""),
            (f"measured/{name}/retried_completions", n_retried,
             "gate (supervised): >= 1, tokens bit-identical to oracle"),
        ]
        if name == "supervised":
            assert n_retried >= 1, "no request exercised the retry path"
            assert stats["gave_up"] == 0
        else:
            assert stats["gave_up"] > 0, "no-retry run never gave up"
    assert frac["supervised"] > frac["no_retry"], (
        f"supervision did not improve completion: {frac['supervised']:.2f} "
        f"vs {frac['no_retry']:.2f}")
    assert np.isfinite(p95["supervised"]), "supervised p95 is not finite"
    assert p95["supervised"] < p95["no_retry"], (
        "supervised p95 not below no-retry")
    rows += [
        ("measured/completed_frac_improvement",
         round((frac["supervised"] - frac["no_retry"]) * 100, 1),
         "percentage points, gate: > 0"),
    ]
    return rows


def _fetch_rows(m, params, fns, prompts, want):
    """Weight-fetch faults: transient absorbed below the supervisor,
    persistent recovered by it — both bit-identical to the oracle."""
    from repro.runtime.faults import FaultPlan, FaultSpec, use_fault_plan
    from repro.runtime.gateway import InvocationRequest

    rows = []
    fn, prompt, oracle = prompts[0][0], prompts[0][1], want[0]
    for name, times in (("transient", 1), ("persistent", 3)):
        rt = _build_runtime(m, params, fns, max_retries=2)
        rt.submit(fn, {}, prompt, 2)     # compile the serve executables
        rt.evict()                       # next submit must re-fork
        baseline = rt.kv_pool_stats()
        # times=1 is under the streamer's fetch_retries budget (2): the
        # fork absorbs it.  times=3 exhausts it: the fork dies and the
        # gateway re-forks.
        plan = FaultPlan([FaultSpec("weight_fetch", at=0, times=times)],
                         seed=SEED)
        with use_fault_plan(plan):
            h = rt.submit(InvocationRequest(fn, prompt,
                                            max_new_tokens=MAX_NEW))
            res = h.result()
        np.testing.assert_array_equal(res.tokens, oracle)
        assert len(plan.fired) == times
        failures = rt.gateway.stats["engine_failures"]
        if name == "transient":
            assert failures == 0, "a transient fetch fault reached the " \
                "supervisor instead of the streamer retry"
        else:
            assert failures == 1 and res.retries == 1, (
                "persistent fetch fault was not recovered by re-fork")
        assert rt.kv_pool_stats() == baseline
        rows.append((f"measured/fetch_{name}/engine_failures", failures,
                     "gate: 0 transient (streamer absorbs), 1 persistent "
                     "(supervisor re-forks); tokens bit-identical"))
    return rows


def measured_rows():
    import jax

    from repro.models.registry import get_smoke_model
    from repro.runtime.engine import Engine

    m = get_smoke_model("smollm-135m", n_layers=2)
    fns = ["fn-a", "fn-b"]
    params = {fn: m.init_params(jax.random.PRNGKey(i))
              for i, fn in enumerate(fns)}
    rng = np.random.default_rng(SEED)
    prompts = [(fns[i % 2],
                rng.integers(0, m.cfg.vocab_size, 6 + i % 3).astype(np.int32))
               for i in range(N_REQ)]
    # the fault-free reference: each request's sequential-engine oracle
    want = [Engine(m, params[fn], donate_cache=False).generate(
                p[None], max_new_tokens=MAX_NEW, cache_len=32).tokens[0]
            for fn, p in prompts]
    return (_crash_rows(m, params, fns, prompts, want)
            + _fetch_rows(m, params, fns, prompts, want))


def main(measured: bool = False):
    rows = analytic_rows()
    if measured:
        mrows = measured_rows()     # raises before returning on gate failure
        rows += mrows
        write_bench_json("fig_fault_recovery", {n: v for n, v, _ in mrows},
                         gates={"supervised_failover_completes_more": True,
                                "retry_token_parity": True})
    return emit(rows)


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main(measured="--measured" in sys.argv)
