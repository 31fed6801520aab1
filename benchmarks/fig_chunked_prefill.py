"""Chunked prefill fused into the decode quantum: short-request p95 TTFT
under an open-loop mix of long cold prompts and short warm requests.

A prompt prefilled to completion inside one engine step head-of-line-
blocks every decode behind it — exactly the cold-start tail TIDAL
targets.  With ``chunk_tokens`` set, each step is a MIXED batch: one
page-multiple chunk of the long prompt advances, then the short
requests' decode slots run, so a short request's first token never
waits for a whole foreign prefill.

Default (analytic): replays one arrival trace through a token-granular
single-server model — whole-prefill vs chunked — with cost-model
prefill/step times, and reports short-request p50/p95 TTFT for both.

``--measured``: drives the LIVE runtime on CPU smoke models through the
real gateway, replaying the identical open-loop schedule with chunking
off and on, and GATES on

  * short-request p95 TTFT strictly lower with chunking enabled, and
  * bit-identical greedy tokens chunked-vs-unchunked for EVERY
    attention family (dense / moe / mla), and vs the sequential engine.
"""

import sys
import time

import numpy as np

from benchmarks.common import PAPER_HW, emit, write_bench_json
from repro.core import costmodel as cm
from repro.core.plans import plan_for

ARCH = "llama3-8b"                 # analytic service times
CHUNK = 256                        # analytic chunk (tokens)
PROMPT_LONG = 2048
FAMILIES = {"dense": "smollm-135m", "moe": "phi3.5-moe-42b-a6.6b",
            "mla": "deepseek-v3-671b"}


# ---------------------------------------------------------------------------
# analytic: one trace, whole-prefill vs chunked
# ---------------------------------------------------------------------------

def _trace(rng, t_long, n_short=12, n_long=4):
    longs = [(i * 0.9 * t_long, "long") for i in range(n_long)]
    shorts, t = [], 0.0
    for _ in range(n_short):
        t += rng.exponential(0.25 * t_long)
        shorts.append((t, "short"))
    return sorted(longs + shorts)


def _simulate(trace, prefill_s, chunk_s, step_s, n_tokens, chunked):
    """Token-granular single server.  Whole: an arriving long prompt
    prefills to completion before anything decodes.  Chunked: each
    rotation spends one chunk of pending prefill, then one decode step
    for every active request."""
    clock, ttfts = 0.0, {"long": [], "short": []}
    pending = list(trace)
    prefilling = []                  # [kind, arrival, chunks_left]
    active = []                      # [kind, tokens_left]
    n_chunks = -(-PROMPT_LONG // CHUNK)
    while pending or prefilling or active:
        if not prefilling and not active:
            clock = max(clock, pending[0][0])
        while pending and pending[0][0] <= clock:
            t, kind = pending.pop(0)
            if not chunked or kind == "short":
                # short prompts fit one chunk: admission-time prefill
                cost = prefill_s if kind == "long" else chunk_s
                clock += cost
                ttfts[kind].append(clock - t)
                active.append([kind, n_tokens[kind] - 1])
            else:
                prefilling.append([kind, t, n_chunks])
        if prefilling:               # one chunk per rotation
            entry = prefilling[0]
            clock += chunk_s
            entry[2] -= 1
            if entry[2] == 0:
                prefilling.pop(0)
                ttfts[entry[0]].append(clock - entry[1])
                active.append([entry[0], n_tokens[entry[0]] - 1])
        for entry in list(active):
            clock += step_s
            entry[1] -= 1
            if entry[1] <= 0:
                active.remove(entry)
    return ttfts


def analytic_rows():
    prefill_s = cm.ttft_execution(plan_for(ARCH, 1, PROMPT_LONG),
                                  PAPER_HW).total
    chunk_s = cm.ttft_execution(plan_for(ARCH, 1, CHUNK), PAPER_HW).total
    step_s = cm.ttft_execution(plan_for(ARCH, 1, 1), PAPER_HW).total
    n_tokens = {"long": 64, "short": 16}
    t_long = prefill_s + n_tokens["long"] * step_s
    trace = _trace(np.random.default_rng(0), t_long)
    rows, p95 = [], {}
    for name, chunked in (("whole", False), ("chunked", True)):
        ttfts = _simulate(trace, prefill_s, chunk_s, step_s, n_tokens,
                          chunked)
        p95[name] = float(np.percentile(ttfts["short"], 95))
        rows += [
            (f"{ARCH}/{name}/p50_short_ttft",
             round(float(np.percentile(ttfts["short"], 50)) * 1e3, 1), ""),
            (f"{ARCH}/{name}/p95_short_ttft", round(p95[name] * 1e3, 1), ""),
            (f"{ARCH}/{name}/p95_long_ttft",
             round(float(np.percentile(ttfts["long"], 95)) * 1e3, 1), ""),
        ]
    rows.append(("p95_short_improvement",
                 round((1 - p95["chunked"] / p95["whole"]) * 100, 1),
                 "percent (paper: 76% better p95 TTFT from taming "
                 "cold-start tails)"))
    return rows


# ---------------------------------------------------------------------------
# measured: live runtime, chunking off vs on, identical arrivals
# ---------------------------------------------------------------------------

def _family_parity_rows():
    """Bit-identical greedy tokens chunked-vs-unchunked (and vs the
    sequential engine) for every attention family."""
    import jax

    from repro.models.registry import get_smoke_model
    from repro.runtime.continuous import ContinuousBatchingEngine
    from repro.runtime.engine import Engine

    rows = []
    for family, arch in FAMILIES.items():
        m = get_smoke_model(arch, n_layers=2)
        params = m.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        reqs = [(rng.integers(1, m.cfg.vocab_size, n).astype(np.int32), k)
                for n, k in [(21, 4), (4, 5), (17, 3)]]
        seq = Engine(m, params, donate_cache=False)
        want = [seq.generate(p[None], max_new_tokens=k,
                             cache_len=32).tokens[0] for p, k in reqs]
        for chunk in (None, 8):
            eng = ContinuousBatchingEngine(m, params, n_slots=2, max_len=32,
                                           page_size=4, chunk_tokens=chunk)
            rids = [eng.submit(p, k) for p, k in reqs]
            out = eng.run()
            for rid, w in zip(rids, want):
                np.testing.assert_array_equal(out[rid].tokens, w)
        rows.append((f"measured/{family}/token_parity", 1,
                     "bit-identical greedy, chunked == unchunked == "
                     "sequential"))
    return rows


def _build_runtime(chunk_tokens, max_len, page, prompt_short):
    import jax

    from repro.core import api as tidal
    from repro.models.registry import get_smoke_model
    from repro.runtime.faas import FaaSRuntime

    # deep enough that a long whole-prompt prefill dwarfs a decode step
    # (~20x on CPU) — the head-of-line blocking chunking removes
    m = get_smoke_model("smollm-135m", n_layers=6)
    params = m.init_params(jax.random.PRNGKey(1))
    rt = FaaSRuntime(n_slots=4, max_len=max_len, page_size=page,
                     trace_seq=prompt_short, chunk_tokens=chunk_tokens)
    rt.deploy(tidal.static_function("fn", m, params), {},
              prewarm_seq=prompt_short)
    return m, params, rt


def measured_rows():
    from repro.runtime.engine import Engine
    from repro.runtime.gateway import InvocationRequest

    rows = _family_parity_rows()

    max_len, page = 320, 64
    len_long, len_short = 256, 8
    new_long, new_short = 8, 4
    rng = np.random.default_rng(0)

    runtimes = {name: _build_runtime(chunk, max_len, page, len_short)
                for name, chunk in (("whole", None), ("chunked", page))}
    m, params, _ = runtimes["whole"]
    vocab = m.cfg.vocab_size
    prompt_long = rng.integers(0, vocab, len_long).astype(np.int32)
    prompt_short = rng.integers(0, vocab, len_short).astype(np.int32)
    seq = Engine(m, params, donate_cache=False)
    want = {
        len_long: seq.generate(prompt_long[None], max_new_tokens=new_long,
                               cache_len=max_len).tokens[0],
        len_short: seq.generate(prompt_short[None], max_new_tokens=new_short,
                                cache_len=max_len).tokens[0]}

    # warm every executable (first long submit pays compilation) so the
    # replay below measures steady-state scheduling, then calibrate the
    # long service time ONCE — both modes replay the identical schedule
    for _, _, rt in runtimes.values():
        rt.submit("fn", {}, prompt_short, new_short)
        rt.submit("fn", {}, prompt_long, new_long)
    t_cal = time.perf_counter()
    runtimes["whole"][2].submit("fn", {}, prompt_long, new_long)
    t_long = time.perf_counter() - t_cal

    # open-loop mix: long cold prompts arriving back-to-back with Poisson
    # short warm requests riding on top of their prefills
    arrivals = [(i * 0.9 * t_long, prompt_long, new_long) for i in range(4)]
    t, srng = 0.0, np.random.default_rng(42)
    for _ in range(16):
        t += float(srng.exponential(0.15 * t_long))
        arrivals.append((t, prompt_short, new_short))
    arrivals.sort(key=lambda a: a[0])

    p95 = {}
    for name, (m, params, rt) in runtimes.items():
        handles = rt.gateway.replay(
            [(due, InvocationRequest("fn", p, max_new_tokens=k))
             for due, p, k in arrivals])
        shorts = []
        for h in handles:
            res = h.result()
            np.testing.assert_array_equal(
                res.tokens, want[len(h.request.prompt)])
            if len(h.request.prompt) == len_short:
                shorts.append(res.ttft_s)
        p95[name] = float(np.percentile(shorts, 95))
        rows += [
            (f"measured/{name}/p50_short_ttft",
             round(float(np.percentile(shorts, 50)) * 1e3, 1), "wall-clock"),
            (f"measured/{name}/p95_short_ttft",
             round(p95[name] * 1e3, 1), "wall-clock"),
        ]
    assert p95["chunked"] < p95["whole"], (
        f"chunked prefill short-request p95 TTFT {p95['chunked']*1e3:.1f}ms "
        f"is not below whole-prefill {p95['whole']*1e3:.1f}ms")
    rows.append(("measured/p95_short_improvement",
                 round((1 - p95["chunked"] / p95["whole"]) * 100, 1),
                 "percent, gate: > 0"))
    return rows


def main(measured: bool = False):
    rows = analytic_rows()
    if measured:
        mrows = measured_rows()     # raises before returning on gate failure
        rows += mrows
        write_bench_json("fig_chunked_prefill", {n: v for n, v, _ in mrows},
                         gates={"chunked_p95_short_below_unchunked": True,
                                "token_parity": True})
    return emit(rows)


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main(measured="--measured" in sys.argv)
