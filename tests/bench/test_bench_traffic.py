"""The traffic generator: seeded, the same work for every seed, lengths and
rates within the mix's parameters."""

import collections
import json
import pathlib

import numpy as np
import pytest

import _benchpath  # noqa: F401
from benchlib import traffic

MIXES = pathlib.Path(_benchpath.BENCH) / "mixes"


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


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
