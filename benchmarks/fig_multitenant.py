"""Dense multi-tenancy on one KV arena: slot-partitioned co-resident
engines vs the old exclusive-arena turn-taking rule.

Default (analytic): N functions of one base model receive a round-robin
request stream.  Under the EXCLUSIVE rule only one engine may hold the
arena, so every tenant switch drains the resident engine and pays a
fresh template fork before the next tenant's prefill.  Co-resident
slot partitions keep every tenant's engine live on the same arena —
after the first fork per tenant, every request is warm.  The simulation
prices both disciplines with the calibrated cost model and reports
aggregate decode throughput and p95 TTFT.

``--measured``: drives the LIVE serving runtime on CPU smoke models —
three functions of ONE model object, hence one shared paged arena —
replaying the identical burst schedule through both disciplines, and
GATES on

  * co-resident aggregate throughput strictly above exclusive-arena
    turn-taking, and
  * co-resident p95 TTFT strictly below turn-taking, and
  * every function's greedy tokens bit-identical to its own
    single-tenant sequential engine (both disciplines), and
  * per-slot adapter-gather decode bit-identical to per-request
    merged-weight dense-LoRA oracles.
"""

import sys
import time

import numpy as np

from benchmarks.common import PAPER_HW, emit, write_bench_json
from repro.core import costmodel as cm
from repro.core.plans import plan_for

ARCH = "llama3-8b"                 # analytic service times
N_FN = 3                           # tenants (the gate needs >= 3)
ROUNDS = 3                         # round-robin passes over the tenants
N_TOK = 16                         # decode tokens per request (analytic)


# ---------------------------------------------------------------------------
# analytic: one request stream, two arena disciplines
# ---------------------------------------------------------------------------

def _analytic_sim(exclusive: bool):
    """FIFO single-server replay of ROUNDS round-robin passes over N_FN
    tenants.  Exclusive: a tenant switch re-forks (the arena was handed
    over); co-resident: only each tenant's FIRST request forks."""
    plan_prefill = plan_for(ARCH, 1, 2048)
    plan_step = plan_for(ARCH, 1, 1)
    prefill_s = cm.ttft_execution(plan_prefill, PAPER_HW).total
    step_s = cm.ttft_execution(plan_step, PAPER_HW).total
    fork_s = cm.ttft_tidal(plan_prefill, PAPER_HW, template_bytes=0).total
    clock, ttfts, resident, forked = 0.0, [], None, set()
    for r in range(ROUNDS):
        for fn in range(N_FN):
            arrival = 0.0                # one burst: queueing delay counts
            if exclusive:
                pays_fork = resident != fn
                resident = fn
            else:
                pays_fork = fn not in forked
                forked.add(fn)
            clock += (fork_s if pays_fork else prefill_s)
            ttfts.append(clock - arrival)
            clock += (N_TOK - 1) * step_s
    n_tokens = ROUNDS * N_FN * N_TOK
    return n_tokens / clock, float(np.percentile(ttfts, 95))


def analytic_rows():
    rows, thr, p95 = [], {}, {}
    for name, exclusive in (("exclusive", True), ("coresident", False)):
        thr[name], p95[name] = _analytic_sim(exclusive)
        rows += [
            (f"{ARCH}/{name}/throughput", round(thr[name], 1),
             "tokens per second"),
            (f"{ARCH}/{name}/p95_ttft", round(p95[name] * 1e3, 1), ""),
        ]
    rows += [
        ("throughput_improvement",
         round((thr["coresident"] / thr["exclusive"] - 1) * 100, 1),
         "percent, model: fork-per-switch amortized away"),
        ("p95_ttft_improvement",
         round((1 - p95["coresident"] / p95["exclusive"]) * 100, 1),
         "percent"),
    ]
    return rows


# ---------------------------------------------------------------------------
# measured: the live runtime, both disciplines, identical arrivals
# ---------------------------------------------------------------------------

def _run_exclusive(rt, arrivals):
    """Turn-taking replay: at most ONE engine is ever resident.  A
    tenant switch drains the resident tenant's handles and evicts its
    engine, so the next tenant pays a fresh fork — the old rule.
    Arrivals are backdated so TTFT counts from the INTENDED arrival."""
    from repro.runtime.gateway import InvocationRequest

    t0 = time.perf_counter()
    resident, pending, results = None, [], []
    for due, fn, prompt, max_new in arrivals:
        while time.perf_counter() - t0 < due:
            time.sleep(0.0005)
        if resident not in (None, fn):
            results += [h.result() for h in pending]
            pending = []
            rt.evict(resident)
        resident = fn
        assert len(rt._engines) <= 1             # the exclusivity invariant
        pending.append(rt.submit(InvocationRequest(
            fn, prompt, max_new_tokens=max_new, arrival_s=t0 + due)))
    results += [h.result() for h in pending]
    return results, time.perf_counter() - t0


def _run_coresident(rt, arrivals):
    from repro.runtime.gateway import InvocationRequest

    t0 = time.perf_counter()
    handles = rt.gateway.replay(
        [(due, InvocationRequest(fn, prompt, max_new_tokens=max_new))
         for due, fn, prompt, max_new in arrivals])
    return [h.result() for h in handles], time.perf_counter() - t0


def _adapter_parity_rows():
    """Per-slot adapter gather vs merged-weight dense-LoRA oracles: the
    shared-base engine serves two adapters and the base from one batch;
    every greedy sequence must be bit-identical to its oracle."""
    import jax
    import jax.numpy as jnp

    from repro.core import api as tidal
    from repro.models.registry import get_smoke_model
    from repro.runtime.engine import Engine
    from repro.runtime.faas import FaaSRuntime
    from repro.runtime.gateway import InvocationRequest

    max_len, path = 48, "blocks.attn.wq"
    m = get_smoke_model("smollm-135m", n_layers=2)
    params = m.init_params(jax.random.PRNGKey(0))
    rt = FaaSRuntime(n_slots=3, max_len=max_len, page_size=8, trace_seq=16,
                     prewarm=False)
    rt.deploy_shared_base(tidal.static_function("base", m, params),
                          n_adapters=4, rank=4, target_paths=(path,))
    alphas = {"ad-1": 0.7, "ad-2": 1.3}
    adapters = {name: tidal.lora_checkpoint(name, m, [path], rank=4, seed=i)
                for i, name in enumerate(alphas, start=1)}
    for name in alphas:
        rt.attach_adapter(name, "base", adapters[name], alpha=alphas[name])

    def merged(adapter, alpha):
        A = np.asarray(adapter.arrays[path + ".A"], np.float32)
        B = np.asarray(adapter.arrays[path + ".B"], np.float32)
        wq = np.asarray(params["blocks"]["attn"]["wq"])
        delta = ((A @ B) * alpha).reshape(wq.shape).astype(wq.dtype)
        return {**params,
                "blocks": {**params["blocks"],
                           "attn": {**params["blocks"]["attn"],
                                    "wq": jnp.asarray(wq + delta)}}}

    rng = np.random.default_rng(7)
    prompts = {name: rng.integers(0, m.cfg.vocab_size, 8).astype(np.int32)
               for name in ("base", "ad-1", "ad-2")}
    oracles = {"base": params}
    oracles.update({n: merged(adapters[n], alphas[n]) for n in alphas})
    want = {n: Engine(m, p, donate_cache=False).generate(
                prompts[n][None], max_new_tokens=8,
                cache_len=max_len).tokens[0]
            for n, p in oracles.items()}
    handles = {n: rt.submit(InvocationRequest(n, p, max_new_tokens=8))
               for n, p in prompts.items()}
    for n, h in handles.items():
        np.testing.assert_array_equal(h.result().tokens, want[n])
    return [("measured/adapter_gather/oracle_mismatches", 0,
             "gate: bit-identical to merged-weight dense LoRA")]


def measured_rows():
    import jax

    from repro.core import api as tidal
    from repro.models.registry import get_smoke_model
    from repro.runtime.engine import Engine
    from repro.runtime.faas import FaaSRuntime

    max_len, page, max_new = 48, 8, 8
    m = get_smoke_model("smollm-135m", n_layers=2)   # ONE object: one arena
    fns = [f"fn-{i}" for i in range(N_FN)]
    params = {fn: m.init_params(jax.random.PRNGKey(i))
              for i, fn in enumerate(fns)}
    rng = np.random.default_rng(0)
    prompts = {fn: rng.integers(0, m.cfg.vocab_size, 6 + i).astype(np.int32)
               for i, fn in enumerate(fns)}
    want = {fn: Engine(m, params[fn], donate_cache=False).generate(
                prompts[fn][None], max_new_tokens=max_new,
                cache_len=max_len).tokens[0]
            for fn in fns}

    # burst schedule: ROUNDS round-robin passes — the order that maximizes
    # the exclusive rule's tenant switches (every request but repeats)
    arrivals = [(i * 0.01, fn, prompts[fn], max_new)
                for i, fn in enumerate(fns * ROUNDS)]

    def build():
        rt = FaaSRuntime(n_slots=N_FN, max_len=max_len, page_size=page,
                         trace_seq=16, prewarm=False)
        for fn in fns:
            rt.deploy(tidal.static_function(fn, m, params[fn]), {})
        for fn in fns:                 # populate the shared jit caches so
            rt.submit(fn, {}, prompts[fn], 2)   # neither run measures XLA
        return rt

    rows, thr, p95 = [], {}, {}
    for name in ("exclusive", "coresident"):
        rt = build()
        if name == "exclusive":
            rt.evict()                 # the old rule keeps nothing resident
            results, wall = _run_exclusive(rt, arrivals)
        else:
            results, wall = _run_coresident(rt, arrivals)
            # the tenants genuinely co-reside: one pool, one lease each
            assert len(rt._pools) == 1
            owners = {w.engine._owner for w in rt._engines.values()}
            assert len(owners) == N_FN
        for res in results:            # token parity gate, both disciplines
            np.testing.assert_array_equal(res.tokens, want[res.fn_name])
        thr[name] = sum(len(r.tokens) for r in results) / wall
        ttfts = sorted(r.ttft_s for r in results)
        p95[name] = float(np.percentile(ttfts, 95))
        rows += [
            (f"measured/{name}/throughput", round(thr[name], 1),
             "tokens per second, wall-clock"),
            (f"measured/{name}/p95_ttft", round(p95[name] * 1e3, 1),
             "wall-clock"),
        ]
    assert thr["coresident"] > thr["exclusive"], (
        f"co-resident throughput {thr['coresident']:.1f} tok/s does not "
        f"beat exclusive turn-taking {thr['exclusive']:.1f} tok/s")
    assert p95["coresident"] < p95["exclusive"], (
        f"co-resident p95 TTFT {p95['coresident']*1e3:.1f}ms is not below "
        f"exclusive turn-taking {p95['exclusive']*1e3:.1f}ms")
    rows += [
        ("measured/throughput_improvement",
         round((thr["coresident"] / thr["exclusive"] - 1) * 100, 1),
         "percent, gate: > 0"),
        ("measured/p95_ttft_improvement",
         round((1 - p95["coresident"] / p95["exclusive"]) * 100, 1),
         "percent, gate: > 0"),
    ]
    rows += _adapter_parity_rows()
    return rows


def main(measured: bool = False):
    rows = analytic_rows()
    if measured:
        mrows = measured_rows()     # raises before returning on gate failure
        rows += mrows
        write_bench_json("fig_multitenant", {n: v for n, v, _ in mrows},
                         gates={"slot_partitioned_beats_exclusive": True,
                                "adapter_gather_parity": True})
    return emit(rows)


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main(measured="--measured" in sys.argv)
