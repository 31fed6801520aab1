"""The serving path's profiler spans and counters, read back from a CPU
trace: a tiny FaaSRuntime forks an engine while its weights stream, then
prefills layer by layer and decodes."""

import threading

import jax
import numpy as np
import pytest

from repro.core import api as tidal
from repro.core.streaming import WeightStreamer
from repro.models.registry import get_smoke_model
from repro.runtime.continuous import ContinuousBatchingEngine
from repro.runtime.faas import FaaSRuntime
from repro.runtime.gateway import InvocationRequest

N_SLOTS, MAX_LEN, MAX_NEW = 2, 32, 4

# (child, parents): every child span lies inside a span of one of its
# parents, on its own thread
NESTING = [
    ("tidal.engine_for", "tidal.submit"),
    ("tidal.fork", "tidal.engine_for"),
    ("tidal.step", "tidal.quantum"),
    ("tidal.prefill", "tidal.step"),
    ("tidal.prefill.layer", "tidal.prefill"),
    # the embedding is waited for before the first layer, the rest of the
    # model when the first decode assembles the full params
    ("tidal.stream.wait", "tidal.prefill|tidal.fork.assemble"),
    ("tidal.sample_first", "tidal.step"),
    ("tidal.arena.write", "tidal.step"),
    ("tidal.decode.dispatch", "tidal.step"),
    ("tidal.fork.assemble", "tidal.decode.dispatch"),
    ("tidal.decode.sync", "tidal.step"),
    ("tidal.emit", "tidal.step"),
]
PER_REQUEST = ["tidal.submit", "tidal.engine_for", "tidal.fork",
               "tidal.stream.upload", "tidal.stream.wait",
               "tidal.fork.assemble", "tidal.prefill", "tidal.arena.write",
               "tidal.sample_first"]


@pytest.fixture(scope="module")
def runtime():
    m = get_smoke_model("smollm-135m", n_layers=2)
    rt = FaaSRuntime(n_slots=N_SLOTS, max_len=MAX_LEN, trace_seq=8,
                     prewarm=False)
    rt.deploy(tidal.static_function(
        "fn", m, m.init_params(jax.random.PRNGKey(0))), {})
    return m, rt


@pytest.fixture(scope="module")
def forked(runtime, tmp_path_factory):
    """One forked request under the trace, its stream held back until
    prefill first asks for a weight, so prefill waits for it; the compile
    counter before and after."""
    from conftest import ProgramTrace
    m, rt = runtime
    upload, get = WeightStreamer._upload, WeightStreamer.get
    asked = threading.Event()

    def held_upload(self, e):
        asked.wait(timeout=10)
        return upload(self, e)

    def asking_get(self, key):
        asked.set()
        return get(self, key)

    WeightStreamer._upload, WeightStreamer.get = held_upload, asking_get
    prompt = np.arange(9, dtype=np.int32) % m.cfg.vocab_size
    before = rt.stats()["compile"]
    tr = ProgramTrace(str(tmp_path_factory.mktemp("trace")))
    try:
        rt.evict("fn")
        with tr:
            res = rt.gateway.submit(InvocationRequest(
                "fn", prompt, max_new_tokens=MAX_NEW)).result()
    finally:
        WeightStreamer._upload, WeightStreamer.get = upload, get
    return res, tr, before, rt.stats()["compile"]


def _inside(child, parent) -> bool:
    return (child.line == parent.line and parent.start <= child.start
            and child.end <= parent.end)


@pytest.mark.parametrize("child,parent", NESTING)
def test_spans_appear_and_nest(forked, child, parent):
    _, tr, _, _ = forked
    kids = tr.named(child)
    assert kids, f"no {child} span"
    outer = [p for name in parent.split("|") for p in tr.named(name)]
    for k in kids:
        assert any(_inside(k, p) for p in outer), (
            f"{child} at {k.start} outside every {parent}")


def test_one_requests_spans_share_its_req_id(forked):
    res, tr, _, _ = forked
    for name in PER_REQUEST:
        assert {s.stats["req_id"] for s in tr.named(name)} == {res.req_id}, (
            name)
    # the uploads ran on the streamer thread, not on the caller's
    main = {s.line for s in tr.named("tidal.submit")}
    assert {s.line for s in tr.named("tidal.stream.upload")}.isdisjoint(main)
    assert {s.stats["kind"] for s in tr.named("tidal.engine_for")} == {
        res.kind}


def test_uploaded_bytes_sum_to_the_streamed_bytes(forked):
    res, tr, _, _ = forked
    ups = tr.named("tidal.stream.upload")
    assert sum(s.stats["nbytes"] for s in ups) == (
        res.fork_stats.streamed_bytes) > 0
    fork, = tr.named("tidal.fork")
    assert fork.stats["streamed_bytes"] == res.fork_stats.streamed_bytes
    layers = {s.stats["layer"] for s in tr.named("tidal.prefill.layer")}
    assert layers == {0, 1}


def test_steps_count_live_rows_and_quanta_count_steps(forked):
    res, tr, _, _ = forked
    steps = tr.named("tidal.step")
    assert {s.stats["rows"] for s in steps} == {N_SLOTS}
    decode = [s for s in steps
              if any(_inside(d, s) for d in tr.named("tidal.decode.sync"))]
    # one live request: the admitting step and MAX_NEW - 2 more decode
    assert len(decode) == MAX_NEW - 1
    assert {s.stats["live"] for s in decode} == {1}
    assert sum(s.stats["admitted"] for s in steps) == 1
    assert sum(q.stats["steps"] for q in tr.named("tidal.quantum")) == len(
        steps)
    prefill, = tr.named("tidal.prefill")
    assert prefill.stats["tokens"] == 9 and prefill.stats["streamed"] == 1


def test_token_stamps_align_with_tokens(forked):
    res, tr, _, _ = forked
    assert len(res.token_times_s) == len(res.tokens) == MAX_NEW
    assert np.all(np.diff(res.token_times_s) >= 0)
    sample, = tr.named("tidal.sample_first")
    assert tr.ns(res.token_times_s[0]) >= sample.end


def test_a_fork_traces_and_lowers_its_prefill_layer_again(forked):
    _, _, before, after = forked

    def layer_compiles(counts):
        return sum(n for (event, fun), (n, _) in counts.items()
                   if "serve_prefill_layer" in fun)

    assert layer_compiles(after) > layer_compiles(before)
    assert sum(n for n, _ in after.values()) > sum(
        n for n, _ in before.values())
    assert all(s >= 0 for _, s in after.values())


def test_request_ids_are_unique_across_engines():
    m = get_smoke_model("smollm-135m", n_layers=1)
    params = m.init_params(jax.random.PRNGKey(0))
    a, b = (ContinuousBatchingEngine(m, params, n_slots=1, max_len=16)
            for _ in range(2))
    prompt = np.arange(3, dtype=np.int32)
    ids = [a.submit(prompt, 1), b.submit(prompt, 1), a.submit(prompt, 1)]
    assert len(set(ids)) == 3 and ids == sorted(ids)
    assert b.submit(prompt, 1, req_id=10**9) == 10**9
