"""The traffic generator: seeded, the same work for every seed, lengths and
rates within the mix's parameters."""

import collections
import json
import pathlib
import time
import types
import weakref

import numpy as np
import pytest

import _benchpath  # noqa: F401
from benchlib import traffic

MIXES = pathlib.Path(_benchpath.BENCH) / "mixes"
# concurrent callers: the toy family's mix (no cell of the benchmark has
# more than one caller yet)
CONCURRENT = pathlib.Path(__file__).parent / "data" / "toy_moe" / "mix.json"


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def _concurrent():
    return json.loads(CONCURRENT.read_text())


def _open(seed, rate=2.0, seconds=60.0):
    return traffic.open_loop(_mix("chat-burst"), 8, 49152, rate, seconds,
                             seed)


def test_same_seed_same_requests():
    a, b = _open(2**33 + 5), _open(2**33 + 5)
    assert [r.fn for r in a] == [r.fn for r in b]
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_reorder_the_same_work():
    a, b = _open(1), _open(2)
    assert [r.fn for r in a] != [r.fn for r in b]
    for key in (lambda r: r.fn, lambda r: len(r.prompt),
                lambda r: r.max_new):
        assert collections.Counter(map(key, a)) == collections.Counter(
            map(key, b))
    assert [r.due for r in a] != [r.due for r in b]


@pytest.mark.parametrize("rate", [0.5, 2.0, 8.0])
def test_open_loop_rate_and_burstiness(rate):
    mix = _mix("chat-burst")
    g = traffic.gaps(mix["arrivals"], rate, 240)
    assert np.mean(g) == pytest.approx(1.0 / rate)
    assert 1.5 < np.std(g) / np.mean(g) < 2.5       # gamma of CV 2
    reqs = _open(7, rate=rate, seconds=120.0)
    assert len(reqs) == round(rate * 120.0)
    dues = [r.due for r in reqs]
    assert dues[0] == 0.0 and dues == sorted(dues) and dues[-1] < 120.0


def test_lengths_within_the_mix_and_prefix_shared_per_function():
    mix = _mix("chat-burst")
    reqs = _open(3)
    tpl = traffic.templates(mix, 8, 49152, 3)
    for r in reqs:
        n = len(r.prompt) - mix["shared_prefix_tokens"]
        assert mix["prompt_tokens"]["min"] <= n <= mix["prompt_tokens"]["max"]
        assert (mix["output_tokens"]["min"] <= r.max_new
                <= mix["output_tokens"]["max"])
        assert np.array_equal(r.prompt[:512], tpl[r.fn])
    lv = traffic.levels(mix["prompt_tokens"])
    assert abs(np.median(lv) - mix["prompt_tokens"]["median"]) < 20
    assert (lv[0], lv[-1]) == (mix["prompt_tokens"]["min"],
                               mix["prompt_tokens"]["max"])


@pytest.mark.parametrize("n", [5, 13, 16, 40])
def test_fewer_or_more_requests_than_levels_span_the_range(n):
    lv = list(range(16))
    got = traffic.spread_evenly(lv, n)
    assert (got[0], got[-1]) == (0, 15) and list(got) == sorted(got)
    counts = collections.Counter(got.tolist())
    assert max(counts.values()) - min(counts.values()) <= 1


def test_zipf_popularity():
    reqs = _open(11, rate=4.0, seconds=100.0)
    counts = collections.Counter(r.fn for r in reqs)
    share = np.asarray([counts[f] for f in range(8)]) / len(reqs)
    want = 1.0 / np.arange(1, 9)
    assert np.allclose(share, want / want.sum(), atol=0.01)


def test_closed_loop_alternates_functions_over_fixed_lengths():
    mix = _mix("scale-to-zero")
    seq = traffic.closed_loop(mix, 2, 151936, 2**31 + 3, n=16)
    assert [r.fn for r in seq] == [0, 1] * 8
    assert {r.max_new for r in seq} == {32}
    lv = traffic.levels(mix["prompt_tokens"])
    assert sorted(len(r.prompt) for r in seq[:len(lv)]) == sorted(lv)
    assert all(128 <= n <= 2048 for n in lv)
    assert (min(lv), max(lv)) == (128, 2048)    # both clip ends are sent
    assert traffic.prompt_levels(mix) == sorted(set(lv))


class _Handle:
    def __init__(self, req):
        self.req, self.done, self.submit_s = req, False, time.perf_counter()

    def result(self, timeout=None):
        return types.SimpleNamespace(
            status="done", ttft_s=0.001, e2e_s=0.002,
            tokens=np.zeros(self.req.max_new, np.int32), reused_prefix_len=0,
            kind="warm", fork_stats=None, token_times_s=None)


class _Gateway:
    """Finishes the oldest request in flight on each waiting pump, counts
    how many were in flight at most, and, like the runtime's gateway,
    keeps finished handles until the next pump.  At each submit no
    finished handle may be held, here or by the loop: a handle holds its
    engine's weights, which a function scaled to zero gives back before
    the next request forks."""

    def __init__(self):
        self.live, self.peak, self.sent = [], 0, []
        self.kept, self.finished = [], []

    def submit(self, req):
        assert not self.kept
        assert all(ref() is None for ref in self.finished)
        h = _Handle(req)
        self.live.append(h)
        self.sent.append(req)
        self.peak = max(self.peak, len(self.live))
        return h

    def pump(self, until=None, timeout=None):
        self.kept = []
        if until is not None:
            h = self.live.pop(0)
            h.done = True
            self.kept.append(h)
            self.finished.append(weakref.ref(h))


def _loop(callers, seed, n=16):
    from benchlib import driver
    mix = dict(_concurrent(), callers=callers)
    seq = traffic.closed_loop(mix, 1, 1000, seed, n=n)
    gw = _Gateway()
    run = types.SimpleNamespace(rt=types.SimpleNamespace(gateway=gw),
                                mix=mix, request=lambda r, arrival: r)
    records, _ = driver.closed_loop(run, seq, 60.0, None)
    return seq, gw, records


@pytest.mark.parametrize("callers", [1, 3, 16])
def test_concurrent_callers_keep_that_many_requests_in_flight(callers):
    seq, gw, records = _loop(callers, 2**33 + 1)
    assert gw.peak == callers
    assert [id(r) for r in gw.sent] == [id(r) for r in seq]   # in order
    assert [r["prompt_len"] for r in records] == [len(r.prompt) for r in seq]
    assert all(r["status"] == "done" for r in records)


def test_concurrent_callers_send_every_seeds_multiset_of_lengths():
    mix = _concurrent()
    got = []
    for seed in (5, 2**33 + 7):
        seq, _, records = _loop(mix["callers"], seed)
        got.append((collections.Counter(r["prompt_len"] for r in records),
                    collections.Counter(r["max_new"] for r in records)))
    assert got[0] == got[1]
    assert sorted(got[0][0]) == traffic.levels(mix["prompt_tokens"])
    assert sorted(got[0][1]) == traffic.levels(mix["output_tokens"])
    # 256 requests per caller unless told otherwise, the first the same
    long = traffic.closed_loop(mix, 1, 1000, 5)
    assert len(long) == 256 * mix["callers"]
    assert [len(r.prompt) for r in long[:16]] == [
        len(r.prompt) for r in traffic.closed_loop(mix, 1, 1000, 5, n=16)]
