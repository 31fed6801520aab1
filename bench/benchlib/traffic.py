"""One general traffic generator, driven by a mix file's parameters.

Every seed gets the same multiset of lengths, functions and inter-arrival
gaps; the seed only decides their order and the prompt tokens.  So the
work a run offers is fixed by the mix and the cell, and two seeds differ
in which request is long and when a burst comes, not in how much there is.
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np
from scipy import stats as sps


@dataclasses.dataclass
class Request:
    fn: int                  # function index
    prompt: np.ndarray       # int32 tokens, shared prefix included
    max_new: int
    due: float = 0.0         # seconds after the window opens (open loop)


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """A NumPy generator from any non-negative integer seed (wider than 32
    bits is fine) and a salt naming its use."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))


def levels(spec: dict) -> list:
    """The fixed set of lengths a length distribution stands for.

    ``{"fixed": n}`` is one length; a lognormal clipped to ``[min, max]``
    gives ``levels`` values at the evenly spaced quantiles ``i / (levels -
    1)`` of the clipped distribution, so the first is ``min`` and the last
    ``max``: both ends of the clipped range are sent."""
    if "fixed" in spec:
        return [int(spec["fixed"])]
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = statistics.NormalDist()
    n = int(spec["levels"])
    out = [int(spec["min"])]
    for i in range(1, n - 1):
        z = nd.inv_cdf(i / (n - 1))
        v = spec["median"] * np.exp(spec["sigma"] * z)
        out.append(int(np.clip(round(v), spec["min"], spec["max"])))
    return out + [int(spec["max"])]


def spread_evenly(values: list, n: int) -> np.ndarray:
    """``n`` items drawn evenly from the sorted ``values``.  With ``n`` at
    least ``len(values)`` every value appears ``n // len(values)`` or one
    more times; with fewer, evenly spaced values from the first to the
    last."""
    k = len(values)
    if n >= k or n == 1:
        idx = [int((i + 0.5) * k / n) for i in range(n)]
    else:
        idx = [round(i * (k - 1) / (n - 1)) for i in range(n)]
    return np.asarray([values[i] for i in idx])


def popularity(spec: dict, n_functions: int) -> np.ndarray:
    """Request share of each function."""
    if spec["kind"] == "zipf":
        w = 1.0 / np.arange(1, n_functions + 1) ** float(spec["s"])
        return w / w.sum()
    if spec["kind"] == "uniform":
        return np.full(n_functions, 1.0 / n_functions)
    raise ValueError(f"unknown popularity {spec['kind']!r}")


def function_counts(shares: np.ndarray, n: int) -> np.ndarray:
    """Integer request counts per function summing to ``n`` (largest
    remainder), every function at least once when ``n`` allows."""
    raw = shares * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts))[: n - counts.sum()]:
        counts[i] += 1
    for i in np.nonzero(counts == 0)[0]:
        if n >= len(shares):
            counts[i] += 1
            counts[np.argmax(counts)] -= 1
    return counts


def gaps(spec: dict, rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps of mean ``1/rate``: a gamma process of the
    given coefficient of variation, as its evenly spaced quantiles."""
    if spec["kind"] == "gamma":
        shape = 1.0 / float(spec["cv"]) ** 2
        q = (np.arange(n) + 0.5) / n
        g = sps.gamma.ppf(q, shape)
    elif spec["kind"] == "uniform":
        g = np.ones(n)
    else:
        raise ValueError(f"unknown arrival process {spec['kind']!r}")
    return g / g.mean() / rate


def templates(mix: dict, n_functions: int, vocab: int, seed: int) -> list:
    """Each function's shared prompt prefix (empty without one)."""
    n = int(mix.get("shared_prefix_tokens", 0))
    return [rng_for(seed, 1, f).integers(0, vocab, n).astype(np.int32)
            for f in range(n_functions)]


def _prompt(tpl: np.ndarray, n_unshared: int, rng, vocab: int) -> np.ndarray:
    tail = rng.integers(0, vocab, n_unshared).astype(np.int32)
    return np.concatenate([tpl, tail])


def open_loop(mix: dict, n_functions: int, vocab: int, rate: float,
              seconds: float, seed: int) -> list:
    """Requests of an open loop over ``seconds`` at ``rate`` per second."""
    n = max(1, int(round(rate * seconds)))
    order = rng_for(seed, 2)
    fns = np.repeat(np.arange(n_functions),
                    function_counts(popularity(mix["popularity"],
                                               n_functions), n))
    fns = order.permutation(fns)
    plen = order.permutation(spread_evenly(levels(mix["prompt_tokens"]), n))
    olen = order.permutation(spread_evenly(levels(mix["output_tokens"]), n))
    g = order.permutation(gaps(mix["arrivals"], rate, n))
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    tpls = templates(mix, n_functions, vocab, seed)
    toks = rng_for(seed, 3)
    return [Request(int(f), _prompt(tpls[f], int(p), toks, vocab), int(o),
                    float(d))
            for f, p, o, d in zip(fns, plen, olen, due)]


def closed_loop(mix: dict, n_functions: int, vocab: int, seed: int,
                n: int | None = None) -> list:
    """The one sequence the closed loop's callers take their requests from,
    in order: functions in turn, each length level once per cycle in a
    seeded order; 256 requests per caller unless ``n`` says."""
    if n is None:
        n = 256 * int(mix.get("callers", 1))
    order = rng_for(seed, 2)
    pl = levels(mix["prompt_tokens"])
    ol = levels(mix["output_tokens"])
    cycle = max(len(pl), len(ol))
    plen, olen = [], []
    while len(plen) < n:
        plen.extend(order.permutation(spread_evenly(pl, cycle)))
        olen.extend(order.permutation(spread_evenly(ol, cycle)))
    tpls = templates(mix, n_functions, vocab, seed)
    toks = rng_for(seed, 3)
    return [Request(i % n_functions,
                    _prompt(tpls[i % n_functions], int(plen[i]), toks, vocab),
                    int(olen[i]))
            for i in range(n)]


def prompt_levels(mix: dict) -> list:
    """Every distinct unshared prompt length the mix can send."""
    return sorted(set(levels(mix["prompt_tokens"])))


def unshared_lengths(mix: dict, requests: list) -> list:
    """The distinct unshared prompt lengths of ``requests``: the shapes a
    run warms up before its window."""
    n = int(mix.get("shared_prefix_tokens", 0))
    return sorted({len(r.prompt) - n for r in requests})
