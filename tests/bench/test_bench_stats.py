"""Metric arithmetic: percentiles with their sample counts, token timelines
and window-edge counting."""

import numpy as np
import pytest

import _benchpath  # noqa: F401
from benchlib import stats


def _rec(due, ttft, e2e, n, p=10, status="done"):
    return {"due": due, "ttft_s": ttft, "e2e_s": e2e, "n_out": n,
            "prompt_len": p, "status": status}


def test_percentiles_and_empty_samples():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 50) == pytest.approx(50.5)
    assert stats.percentile(vals, 95) == pytest.approx(95.05)
    assert stats.percentile([], 95) is None
    assert stats.percentile([3.0], 95) == 3.0


def test_spread_is_the_interquartile_range_over_the_median():
    v = [10, 11, 12, 13, 14, 15]
    q1, q2, q3 = 10.75, 12.5, 14.25
    assert stats.spread(v) == pytest.approx((q3 - q1) / q2)


def test_token_times_spread_from_first_token_to_completion():
    r = _rec(due=1.0, ttft=0.5, e2e=2.5, n=5)
    assert np.allclose(stats.token_times(r), [1.5, 2.0, 2.5, 3.0, 3.5])
    assert np.allclose(stats.token_times(_rec(0, 0.2, 0.2, 1)), [0.2])


def test_window_edges_count_straddling_requests_by_their_token_times():
    recs = [_rec(0.0, 0.5, 2.5, 5),          # tokens at .5 1 1.5 2 2.5
            _rec(9.0, 0.5, 1.5, 3),          # tokens at 9.5 10 10.5
            _rec(3.0, 1.0, 1.0, 1, status="failed")]
    assert stats.tokens_in(recs, 0.0, 10.0) == 6
    assert stats.tokens_in(recs, 1.0, 2.0) == 2
    # decode tokens (index >= 1) at positions prompt_len + i - 1
    assert stats.decode_tokens_in(recs, 0.0, 10.0) == [10, 11, 12, 13]
