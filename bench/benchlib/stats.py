"""Metric arithmetic over request records: percentiles, token timelines and
window-edge counting."""

from __future__ import annotations

import statistics

import numpy as np


def percentile(values, q: float):
    """The q-th percentile (linear interpolation between order statistics);
    None for an empty sample."""
    vals = [float(v) for v in values]
    if not vals:
        return None
    return float(np.percentile(np.asarray(vals), q))


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, q2, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return (q3 - q1) / q2


def done(records) -> list:
    return [r for r in records if r["status"] == "done"]


def token_times(r) -> np.ndarray:
    """Seconds (on the window's clock) at which a finished request's output
    tokens reached the host: the first at its first-token time, the rest
    spread evenly up to its completion (the runtime stamps no per-token
    times)."""
    n = int(r["n_out"])
    first = r["due"] + r["ttft_s"]
    last = r["due"] + r["e2e_s"]
    if n <= 1:
        return np.asarray([first])[:n]
    return first + (last - first) * np.arange(n) / (n - 1)


def tokens_in(records, start: float, end: float) -> int:
    """Output tokens of finished requests that reached the host in
    ``[start, end)``."""
    n = 0
    for r in done(records):
        t = token_times(r)
        n += int(np.count_nonzero((t >= start) & (t < end)))
    return n


def decode_tokens_in(records, start: float, end: float) -> list:
    """Positions written by the decode steps whose tokens reached the host
    in ``[start, end)``: token ``i >= 1`` of a request with a ``p``-token
    prompt was decoded at position ``p + i - 1`` over ``p + i`` keys."""
    out = []
    for r in done(records):
        t = token_times(r)
        idx = np.nonzero((t >= start) & (t < end))[0]
        out.extend(int(r["prompt_len"]) + int(i) - 1 for i in idx if i >= 1)
    return out
