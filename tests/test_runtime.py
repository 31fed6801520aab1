"""Continuous-batching serving runtime: pool correctness, token parity with
the sequential Engine, ForkSession admission mid-stream, the FaaS front-end
service classes, and the scheduler's measured mode."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api as tidal
from repro.core.scheduler import (ClusterSim, FunctionProfile,
                                  SchedulerConfig, make_trace, summarize)
from repro.core.streaming import ForkSession, StreamEntry, WeightStreamer
from repro.core.template_server import TemplateServer
from repro.models.registry import get_smoke_model
from repro.runtime.continuous import ContinuousBatchingEngine
from repro.runtime.engine import Engine
from repro.runtime.faas import FaaSRuntime, measure_service_times
from repro.runtime.kv_pool import (KVCachePool, PagedKVCachePool,
                                   PoolExhausted)
from repro.utils import path_str

MAX_LEN = 24


def _mixed_requests(vocab, seed=3):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, s).astype(np.int32), n)
            for s, n in [(4, 5), (9, 3), (6, 7), (11, 4), (5, 6)]]


def _sequential_tokens(m, params, reqs):
    eng = Engine(m, params, donate_cache=False)
    return [eng.generate(p[None], max_new_tokens=n,
                         cache_len=MAX_LEN).tokens[0] for p, n in reqs]


# ---------------------------------------------------------------------------
# KVCachePool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["smollm-135m", "deepseek-v3-671b",
                                  "zamba2-2.7b"])
def test_kv_pool_scatter_gather_roundtrip(arch):
    m = get_smoke_model(arch)
    pool = KVCachePool(m, n_slots=3, max_len=8)
    subs = []
    for slot in range(3):
        sub = jax.tree.map(
            lambda t: jnp.full(t.shape, slot + 1, t.dtype),
            m.make_cache(1, 8))
        subs.append(sub)
        pool.write_slot(slot, sub)
    for slot in (2, 0, 1):
        got = pool.read_slot(slot)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(subs[slot])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kv_pool_slot_accounting():
    m = get_smoke_model("smollm-135m", n_layers=1)
    pool = KVCachePool(m, n_slots=2, max_len=4)
    a, b = pool.alloc(), pool.alloc()
    assert pool.n_free == 0
    with pytest.raises(RuntimeError):
        pool.alloc()
    pool.release(a)
    assert pool.n_free == 1
    with pytest.raises(ValueError):
        pool.release(a)                      # double free
    assert pool.alloc() == a


def test_kv_pool_release_after_realloc():
    """Regression for the free-set tracking: a slot that was released and
    re-allocated must release cleanly again, and double-release must still
    raise regardless of interleaving."""
    m = get_smoke_model("smollm-135m", n_layers=1)
    pool = KVCachePool(m, n_slots=4, max_len=4)
    slots = [pool.alloc() for _ in range(4)]
    for s in slots:
        pool.release(s)
    again = pool.alloc()
    pool.release(again)
    with pytest.raises(ValueError):
        pool.release(again)
    assert pool.n_free == 4


# ---------------------------------------------------------------------------
# PagedKVCachePool (block allocator)
# ---------------------------------------------------------------------------

def _paged_pool(n_slots=3, max_len=24, page_size=8, n_pages=None, arch="smollm-135m"):
    m = get_smoke_model(arch, n_layers=1)
    return PagedKVCachePool(m, n_slots=n_slots, max_len=max_len,
                            page_size=page_size, n_pages=n_pages)


def test_paged_pool_rejects_recurrent_families():
    m = get_smoke_model("xlstm-1.3b")
    with pytest.raises(ValueError, match="paged"):
        PagedKVCachePool(m, n_slots=2, max_len=16)


def test_paged_pool_exhaustion_raises_instead_of_hanging():
    """Admission pressure must surface as PoolExhausted, never a free-list
    wait: no free slot, or not enough unreserved pages."""
    pool = _paged_pool(n_slots=2, max_len=24, page_size=8, n_pages=5)
    a = pool.alloc(prompt_len=8, max_new_tokens=8)       # reserves 2 of 4
    assert not pool.can_admit(24)                        # 3 > 2 available
    with pytest.raises(PoolExhausted):
        pool.alloc(prompt_len=16, max_new_tokens=8)
    b = pool.alloc(prompt_len=8, max_new_tokens=8)       # exactly fits
    with pytest.raises(PoolExhausted):                   # no slot either
        pool.alloc(prompt_len=1, max_new_tokens=1)
    pool.release(b)
    pool.release(a)
    # a request wider than a slot's page table can never be admitted...
    with pytest.raises(ValueError, match="page table"):
        pool.alloc(prompt_len=32, max_new_tokens=9)
    # ...nor one that fits a page table but not this (undersized) arena
    tiny = _paged_pool(n_slots=2, max_len=24, page_size=8, n_pages=3)
    with pytest.raises(ValueError, match="allocatable"):
        tiny.alloc(prompt_len=17, max_new_tokens=7)


def test_paged_pool_free_list_reuse_after_retirement():
    pool = _paged_pool(n_slots=2, max_len=24, page_size=8, n_pages=7)
    a = pool.alloc(prompt_len=17, max_new_tokens=7)      # 3 blocks
    pool.ensure_len(a, 17)
    used = set(pool.page_table[a, :3].tolist())
    assert pool.NULL_PAGE not in used and len(used) == 3
    pool.release(a)
    assert pool.n_free_pages == 6 and pool.n_available_pages == 6
    b = pool.alloc(prompt_len=24, max_new_tokens=0)
    pool.ensure_len(b, 24)
    assert set(pool.page_table[b, :3].tolist()) <= used | {4, 5, 6}
    assert pool.n_available_pages == 3


def test_paged_pool_fragmentation_mixed_lengths():
    """Fixed-size pages can't fragment: after any interleaving of
    mixed-length allocs and frees, every page is recovered and a
    full-arena request still fits."""
    pool = _paged_pool(n_slots=4, max_len=32, page_size=8, n_pages=13)
    rng = np.random.default_rng(0)
    live = {}
    for it in range(50):
        if live and (len(live) == 4 or rng.random() < 0.5):
            slot = live.pop(rng.choice(list(live)))
            pool.release(slot)
        else:
            n_tok = int(rng.integers(1, 33))
            if pool.can_admit(n_tok):
                slot = pool.alloc(n_tok, 0)
                pool.ensure_len(slot, n_tok)
                live[f"r{it}"] = slot
    for slot in live.values():
        pool.release(slot)
    assert pool.n_free_pages == 12 and pool.n_available_pages == 12
    # no leak: one request can still claim every allocatable page
    s = pool.alloc(prompt_len=32, max_new_tokens=0)      # 4 blocks
    pool.ensure_len(s, 32)
    assert len(set(pool.page_table[s, :4].tolist())) == 4


def test_paged_pool_write_read_roundtrip():
    """write_prompt -> read_slot must reproduce the dense sub-cache's
    occupied prefix for both GQA and MLA cache layouts."""
    for arch in ("smollm-135m", "deepseek-v3-671b"):
        m = get_smoke_model(arch, n_layers=2)
        pool = PagedKVCachePool(m, n_slots=2, max_len=16, page_size=4)
        n_tok = 10
        sub = jax.tree.map(
            lambda t: jnp.arange(t.size, dtype=t.dtype).reshape(t.shape),
            m.make_cache(1, pool.padded_len))
        slot = pool.alloc(n_tok, 4)
        pool.write_prompt(slot, sub, n_tok)
        got = pool.read_slot(slot, n_tok)
        nb = pool.blocks_for(n_tok) * pool.page_size
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(sub)):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b[:, :, :nb]))


# ---------------------------------------------------------------------------
# ContinuousBatchingEngine vs sequential Engine
# ---------------------------------------------------------------------------

def test_continuous_matches_sequential_mixed_lengths():
    """Bit-identical greedy tokens for a mixed-length request set, with
    fewer slots than requests (slot reuse + mid-decode admission)."""
    m = get_smoke_model("smollm-135m", n_layers=2)
    params = m.init_params(jax.random.PRNGKey(0))
    reqs = _mixed_requests(m.cfg.vocab_size)
    want = _sequential_tokens(m, params, reqs)

    cbe = ContinuousBatchingEngine(m, params, n_slots=2, max_len=MAX_LEN)
    rids = [cbe.submit(p, n) for p, n in reqs]
    out = cbe.run()
    for rid, (p, n), w in zip(rids, reqs, want):
        assert out[rid].n_generated == n
        assert out[rid].prompt_len == len(p)
        np.testing.assert_array_equal(out[rid].tokens, w)


@pytest.mark.parametrize("arch", ["deepseek-v3-671b", "zamba2-2.7b",
                                  "xlstm-1.3b"])
def test_continuous_matches_sequential_other_families(arch):
    m = get_smoke_model(arch)
    params = m.init_params(jax.random.PRNGKey(0))
    reqs = _mixed_requests(m.cfg.vocab_size, seed=1)[:3]
    want = _sequential_tokens(m, params, reqs)
    cbe = ContinuousBatchingEngine(m, params, n_slots=2, max_len=MAX_LEN)
    rids = [cbe.submit(p, n) for p, n in reqs]
    out = cbe.run()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid].tokens, w)


@pytest.mark.parametrize("arch", ["smollm-135m", "phi3.5-moe-42b-a6.6b",
                                  "deepseek-v3-671b"])
def test_paged_engine_matches_sequential_per_family(arch):
    """The paged pool (page tables + incremental page mapping) must keep
    greedy output bit-identical to the sequential dense Engine for every
    attention family: dense (GQA), moe, and MLA latent caches."""
    m = get_smoke_model(arch, n_layers=2)
    params = m.init_params(jax.random.PRNGKey(2))
    reqs = _mixed_requests(m.cfg.vocab_size, seed=13)
    want = _sequential_tokens(m, params, reqs)
    cbe = ContinuousBatchingEngine(m, params, n_slots=2, max_len=MAX_LEN,
                                   page_size=8)
    assert cbe.paged and isinstance(cbe.pool, PagedKVCachePool)
    rids = [cbe.submit(p, n) for p, n in reqs]
    out = cbe.run()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid].tokens, w)


def test_paged_engine_under_page_pressure():
    """An arena far smaller than n_slots*max_len (the dense footprint)
    still drains a mixed workload bit-identically: admission defers on
    page pressure and retirement's freed pages unblock it."""
    m = get_smoke_model("smollm-135m", n_layers=2)
    params = m.init_params(jax.random.PRNGKey(0))
    reqs = _mixed_requests(m.cfg.vocab_size, seed=21)
    want = _sequential_tokens(m, params, reqs)
    # 6 allocatable pages of 8 = 48 token slots, vs dense 3*24 = 72
    cbe = ContinuousBatchingEngine(m, params, n_slots=3, max_len=MAX_LEN,
                                   page_size=8, n_pages=7)
    assert cbe.pool.nbytes() < KVCachePool(m, 3, MAX_LEN).nbytes()
    rids = [cbe.submit(p, n) for p, n in reqs]
    out = cbe.run()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid].tokens, w)


def test_paged_engine_rejects_unservable_request():
    m = get_smoke_model("smollm-135m", n_layers=1)
    cbe = ContinuousBatchingEngine(m, m.init_params(jax.random.PRNGKey(0)),
                                   n_slots=2, max_len=32, page_size=8,
                                   n_pages=3)
    with pytest.raises(ValueError, match="pages"):
        cbe.submit(np.zeros(20, np.int32), max_new_tokens=4)  # needs 3 > 2


def test_paged_default_tracks_family():
    """Attention families page by default; recurrent-state families keep
    the dense slot pool (constant-size state), opt-out works."""
    dense = get_smoke_model("smollm-135m", n_layers=1)
    ssm = get_smoke_model("zamba2-2.7b")
    p = dense.init_params(jax.random.PRNGKey(0))
    assert ContinuousBatchingEngine(dense, p, n_slots=1, max_len=8).paged
    assert not ContinuousBatchingEngine(
        dense, p, n_slots=1, max_len=8, paged=False).paged
    assert not ContinuousBatchingEngine(
        ssm, ssm.init_params(jax.random.PRNGKey(0)), n_slots=1,
        max_len=8).paged
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(ssm, None, n_slots=1, max_len=8, paged=True)


def test_continuous_rejects_oversized_and_encdec():
    m = get_smoke_model("smollm-135m", n_layers=1)
    cbe = ContinuousBatchingEngine(m, m.init_params(jax.random.PRNGKey(0)),
                                   n_slots=1, max_len=8)
    with pytest.raises(ValueError):
        cbe.submit(np.zeros(6, np.int32), max_new_tokens=4)   # 6+4 > 8
    enc = get_smoke_model("whisper-medium")
    with pytest.raises(NotImplementedError):
        ContinuousBatchingEngine(enc, None)


def _slow_fork_session(m, params, delay_s=0.003):
    """A ForkSession whose weights stream with an artificial per-leaf delay,
    so admission reliably happens while later layers are still in flight."""
    flat = {path_str(p): np.asarray(l)
            for p, l in jax.tree_util.tree_leaves_with_path(params)}

    def fetch(arr):
        time.sleep(delay_s)
        return arr

    entries = [StreamEntry((path, ()), fetch=lambda a=arr: fetch(a))
               for path, arr in flat.items()]
    streamer = WeightStreamer(entries, {}, {}).start()
    return ForkSession(m, streamer, {path: ("whole",) for path in flat})


def test_admission_from_fork_session_mid_stream():
    """A request admitted while the session's weights are still streaming
    (layer-streamed prefill) must yield the same tokens as plain params —
    and the rest of the mixed batch must stay bit-identical too."""
    m = get_smoke_model("smollm-135m", n_layers=3)
    params = m.init_params(jax.random.PRNGKey(0))
    reqs = _mixed_requests(m.cfg.vocab_size, seed=7)
    want = _sequential_tokens(m, params, reqs)

    session = _slow_fork_session(m, params)
    cbe = ContinuousBatchingEngine(m, session, n_slots=2, max_len=MAX_LEN)
    rids = [cbe.submit(p, n) for p, n in reqs]
    out = cbe.run()
    # first admission happened while the stream was in flight
    assert out[rids[0]].streamed_prefill
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid].tokens, w)


def test_forked_session_from_template_server_parity():
    """End-to-end: TemplateServer.fork -> continuous batching == Engine."""
    m = get_smoke_model("smollm-135m", n_layers=3)
    params = m.init_params(jax.random.PRNGKey(0))
    srv = TemplateServer(trace_batch=1, trace_seq=8)
    srv.register(tidal.static_function("f", m, params), {})
    session, _ = srv.fork("f", {})
    reqs = _mixed_requests(m.cfg.vocab_size, seed=11)[:3]
    want = _sequential_tokens(m, params, reqs)
    cbe = ContinuousBatchingEngine(m, session, n_slots=2, max_len=MAX_LEN)
    rids = [cbe.submit(p, n) for p, n in reqs]
    out = cbe.run()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(out[rid].tokens, w)


# ---------------------------------------------------------------------------
# FaaSRuntime + measured-mode scheduler
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def faas_runtime():
    m = get_smoke_model("smollm-135m", n_layers=2)
    rt = FaaSRuntime(n_slots=2, max_len=MAX_LEN, trace_seq=8)
    params = m.init_params(jax.random.PRNGKey(0))
    rt.deploy(tidal.static_function("fn-static", m, params), {},
              prewarm_seq=8)
    rt.deploy(tidal.lora_function("fn-lora", m, params,
                                  ["blocks.attn.wq"], n_adapters=2),
              {"adapter": "adapter-0"}, prewarm_seq=8)
    return m, params, rt


def test_faas_service_classes_and_parity(faas_runtime):
    m, params, rt = faas_runtime
    prompt = np.arange(10, dtype=np.int32) % m.cfg.vocab_size
    want = Engine(m, params, donate_cache=False).generate(
        prompt[None], max_new_tokens=4, cache_len=MAX_LEN).tokens[0]

    r1 = rt.submit("fn-static", {}, prompt, 4)      # first invocation
    r2 = rt.submit("fn-static", {}, prompt, 4)      # engine kept alive
    rt.evict("fn-static")                           # keep-alive expiry
    r3 = rt.submit("fn-static", {}, prompt, 4)      # re-fork
    assert (r1.kind, r2.kind, r3.kind) == ("cold", "warm", "fork")
    assert r1.fork_stats is not None and r2.fork_stats is None
    for r in (r1, r2, r3):
        np.testing.assert_array_equal(r.tokens, want)

    with pytest.raises(KeyError):
        rt.submit("nope", {}, prompt, 4)


def test_faas_submit_many_shares_one_engine(faas_runtime):
    """submit_many enqueues every request before any engine drains: same-
    (fn, event) requests share one continuous-batching engine and decode
    together, and each output stays bit-identical to a sequential run."""
    m, params, rt = faas_runtime
    rt.evict()
    reqs = _mixed_requests(m.cfg.vocab_size, seed=5)[:3]
    want = _sequential_tokens(m, params, reqs)
    results = rt.submit_many([("fn-static", {}, p, n) for p, n in reqs])
    # one fork, then the batch-mates found the same engine already warm
    assert results[0].kind in ("cold", "fork")
    assert [r.kind for r in results[1:]] == ["warm", "warm"]
    assert len([k for k in rt.warm_engines() if k[0] == "fn-static"]) == 1
    for r, w in zip(results, want):
        np.testing.assert_array_equal(r.tokens, w)


def test_faas_submit_many_validates_before_enqueue(faas_runtime):
    """A bad batch member fails the whole call BEFORE anything is enqueued
    or forked: no orphaned requests, no misclassified invocations, and
    collected results don't accumulate on warm engines."""
    m, params, rt = faas_runtime
    good = np.arange(6, dtype=np.int32)
    too_long = np.arange(MAX_LEN, dtype=np.int32)
    with pytest.raises(ValueError, match="exceeds runtime max_len"):
        rt.submit_many([("fn-static", {}, good, 4),
                        ("fn-static", {}, too_long, 4)])
    with pytest.raises(KeyError):
        rt.submit_many([("fn-static", {}, good, 4),
                        ("not-deployed", {}, good, 4)])
    r = rt.submit("fn-static", {}, good, 4)
    assert r.tokens.shape == (4,)
    for key in rt.warm_engines():
        eng = rt._engines[key].engine
        assert eng.n_pending == 0          # nothing orphaned in queues
        assert not eng.results             # collected results are popped


def test_faas_ttft_includes_fork_time(faas_runtime, profiled):
    """Fork/cold TTFT must cover the synchronous fork, not just
    prefill+decode — that is the number Eq. 1 and measured mode consume:
    the first token reaches the handle after the request's ``tidal.fork``
    span ends."""
    m, params, rt = faas_runtime
    prompt = np.arange(6, dtype=np.int32)
    rt.evict("fn-static")
    with profiled:
        forked = rt.submit("fn-static", {}, prompt, 2)
    warm = rt.submit("fn-static", {}, prompt, 2)
    assert forked.kind == "fork" and warm.kind == "warm"
    fork, = [s for s in profiled.named("tidal.fork")
             if s.stats["req_id"] == forked.req_id]
    assert profiled.ns(forked.token_times_s[0]) > fork.end
    assert forked.ttft_s * 1e9 > fork.end - fork.start


def test_faas_deploy_prewarms_engine_entry_points(faas_runtime):
    """deploy() pre-compiles the engine's serve entry points (shared per
    model), so the executable cache holds exactly one prefill + one decode
    signature for the shared smoke model."""
    m, params, rt = faas_runtime
    kinds = {k[1] for k in rt.exe_cache.keys()}
    assert kinds == {"prefill", "decode-pool"}
    assert rt.exe_cache.stats.misses == 2          # dedup'd across functions
    assert rt.exe_cache.stats.hits >= 1            # 2nd deploy hit the cache


def test_faas_lora_adapters_get_separate_engines(faas_runtime):
    m, params, rt = faas_runtime
    prompt = np.arange(8, dtype=np.int32) % m.cfg.vocab_size
    a0 = rt.submit("fn-lora", {"adapter": "adapter-0"}, prompt, 4)
    a1 = rt.submit("fn-lora", {"adapter": "adapter-1"}, prompt, 4)
    again = rt.submit("fn-lora", {"adapter": "adapter-1"}, prompt, 4)
    assert a1.kind in ("cold", "fork") and again.kind == "warm"
    np.testing.assert_array_equal(a1.tokens, again.tokens)
    # different adapters are different dynamic weights -> usually different
    # engines; both decode greedily from the same base so shapes agree
    assert a0.tokens.shape == a1.tokens.shape


def test_faas_evict_returns_slots_and_pages_to_pool():
    """Regression: engines borrow slots/pages from runtime-owned shared
    pools (one arena per instance+model), so eviction must hand back
    everything an engine still holds.  Repeated serve→evict cycles keep
    every free count at its initial value, and evicting an engine with
    undrained work releases its slots/pages."""
    m = get_smoke_model("smollm-135m", n_layers=1)      # paged pool
    s = get_smoke_model("zamba2-2.7b")                  # dense slot pool
    rt = FaaSRuntime(n_slots=2, max_len=MAX_LEN, trace_seq=8)
    rt.deploy(tidal.static_function("f-att", m,
                                    m.init_params(jax.random.PRNGKey(0))),
              {}, prewarm_seq=8)
    rt.deploy(tidal.static_function("f-ssm", s,
                                    s.init_params(jax.random.PRNGKey(0))),
              {}, prewarm_seq=8)
    prompt = np.arange(6, dtype=np.int32)
    rt.submit("f-att", {}, prompt, 2)
    rt.submit("f-ssm", {}, prompt, 2)
    baseline = rt.kv_pool_stats()
    assert baseline and all(st["n_free_slots"] == 2
                            for st in baseline.values())
    for _ in range(3):
        rt.submit("f-att", {}, prompt, 2)
        rt.submit("f-ssm", {}, prompt, 2)
        assert rt.evict() == 2
        assert rt.kv_pool_stats() == baseline           # no arena leak
    # an engine evicted while it still HOLDS slots (admitted, not drained)
    # must return them — this is the leak the shared arena would otherwise
    # accumulate across keep-alive expiries
    _, engine, _, _ = rt._engine_for("f-att", {}, time.perf_counter())
    engine.submit(prompt, 4)
    engine.step()                          # admit -> slot + prompt pages
    assert rt.kv_pool_stats() != baseline
    rt.evict("f-att")
    assert rt.kv_pool_stats() == baseline


def test_shared_pool_engines_interleave_via_partition_leases():
    """Slot-partition leases dissolve the old exclusive-arena rule:
    engines sharing one paged pool hold disjoint partitions, decode
    against owner-masked page tables (foreign rows read as free), and may
    step interleaved mid-decode without corrupting each other's KV."""
    m = get_smoke_model("smollm-135m", n_layers=1)
    params = m.init_params(jax.random.PRNGKey(0))
    pool = PagedKVCachePool(m, n_slots=2, max_len=16, page_size=8)
    a = ContinuousBatchingEngine(m, params, pool=pool)
    b = ContinuousBatchingEngine(m, params, pool=pool)
    ra = a.submit(np.arange(4, dtype=np.int32), 4)
    a.step()                               # a holds a slot mid-decode
    rb = b.submit(np.arange(4, dtype=np.int32), 2)
    b.step()                               # co-tenant steps concurrently
    out_a = a.run()                        # a drains -> slots come back
    out_b = b.run()
    assert out_a[ra].n_generated == 4 and out_b[rb].n_generated == 2
    # same prompt + greedy: b's tokens must prefix a's, or a step leaked
    np.testing.assert_array_equal(out_b[rb].tokens, out_a[ra].tokens[:2])
    assert pool.n_free_slots == 2


def test_faas_engines_of_one_model_share_one_pool():
    """Two functions over the same model draw slots from ONE shared arena
    (allocated once per instance), not one arena per engine fork."""
    m = get_smoke_model("smollm-135m", n_layers=1)
    params = m.init_params(jax.random.PRNGKey(0))
    rt = FaaSRuntime(n_slots=2, max_len=MAX_LEN, trace_seq=8)
    rt.deploy(tidal.static_function("f-one", m, params), {}, prewarm_seq=8)
    rt.deploy(tidal.static_function("f-two", m, params), {}, prewarm_seq=8)
    prompt = np.arange(6, dtype=np.int32)
    rt.submit("f-one", {}, prompt, 2)
    rt.submit("f-two", {}, prompt, 2)
    assert len(rt._pools) == 1
    e1 = rt._engines[("f-one", ())].engine
    e2 = rt._engines[("f-two", ())].engine
    assert e1.pool is e2.pool


def test_cluster_sim_measured_mode():
    """ClusterSim in measured mode: warm/fork/cold service times come from
    the live runtime's wall clock, not the analytic oracle."""
    from repro.core.plans import plan_for

    m = get_smoke_model("smollm-135m", n_layers=1)
    rt = FaaSRuntime(n_slots=2, max_len=MAX_LEN, trace_seq=8)
    params = m.init_params(jax.random.PRNGKey(1))
    rt.deploy(tidal.lora_function("fn-live", m, params,
                                  ["blocks.attn.wq"], n_adapters=2),
              {"adapter": "adapter-0"}, prewarm_seq=8)
    mst = measure_service_times(rt, {"fn-live": {"adapter": "adapter-1"}},
                                prompt_len=8, max_new_tokens=2)
    for kind in ("warm", "fork", "cold"):
        assert mst.service_s("fn-live", kind) is not None
    assert mst.service_s("fn-live", "warm") < mst.service_s("fn-live", "fork")

    plan = plan_for("smollm-135m", 1, 867)
    fns = {"fn-live": FunctionProfile(
        name="fn-live",
        plan_for_len=lambda L: plan_for("smollm-135m", 1, L),
        dynamic_bytes=1 << 20, model_bytes=plan.total_weight_bytes)}
    trace = make_trace({"fn-live": 2.0}, duration_s=10.0,
                       fn_tasks={"fn-live": "mail"}, seed=0)
    cfg = SchedulerConfig(n_gpus=2, policy="tidal", dk=True, keep_alive_s=5.0,
                          measured=mst)
    results = ClusterSim(cfg, fns).run(trace)
    assert results
    for r in results:
        if not r.rejected:
            assert r.service_s == pytest.approx(
                mst.service_s("fn-live", r.kind))
    s = summarize(results)
    assert s["warm"] + s["fork"] + s["cold"] == s["n"] - s["rejected"]


def test_cluster_sim_measured_falls_back_to_analytic():
    """Functions absent from the measured table use the analytic oracle."""
    from repro.core.plans import plan_for

    class Empty:
        def service_s(self, fn, kind, input_len=None):
            return None

    plan = plan_for("smollm-135m", 1, 867)
    fns = {"f": FunctionProfile(
        name="f", plan_for_len=lambda L: plan_for("smollm-135m", 1, L),
        model_bytes=plan.total_weight_bytes)}
    trace = make_trace({"f": 1.0}, duration_s=5.0, fn_tasks={"f": "mail"},
                       seed=1)
    base = ClusterSim(SchedulerConfig(n_gpus=1), fns).run(trace)
    meas = ClusterSim(SchedulerConfig(n_gpus=1, measured=Empty()),
                      fns).run(trace)
    assert [r.service_s for r in base] == [r.service_s for r in meas]
