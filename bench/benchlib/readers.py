"""Shared arithmetic of the metric readers in ``bench/metrics/``."""

from __future__ import annotations

import bisect

from benchlib import stats, trace


def ttft_ms(ctx, q: float):
    """q-th percentile of time to first token over the window's finished
    requests, from each request's intended arrival."""
    return stats.percentile(
        [r["ttft_s"] * 1e3 for r in stats.done(ctx.records)], q)


def tpot_ms(ctx, q: float):
    """q-th percentile over finished requests of their mean gap between
    output tokens after the first."""
    return stats.percentile(
        [(r["e2e_s"] - r["ttft_s"]) * 1e3 / (r["n_out"] - 1)
         for r in stats.done(ctx.records) if r["n_out"] > 1], q)


def idle_share(ctx):
    """Percent of the traced slice in which no operation ran on the
    device, averaged over the chips."""
    if ctx.trace is None or not ctx.trace.ops:
        return None
    lo, hi = ctx.trace_ns
    busy = sum(trace.busy_ns(o, lo, hi) for o in ctx.trace.ops)
    return 100.0 * (1.0 - busy / len(ctx.trace.ops) / (hi - lo))


def kernel_events(ctx, signature: str) -> list:
    """The slice's device ops of one kernel, found by the regular
    expression ``signature`` on the op's whole HLO text, after
    ``{rows}`` in it is replaced by the cell's ``n_slots``."""
    if ctx.trace is None or not ctx.trace.ops:
        return []
    return trace.matching_text(
        ctx.trace.ops[0], signature.format(rows=ctx.cell["n_slots"]),
        *ctx.trace_ns)


def decode_calls(ctx, signature: str):
    """Batched decode steps in the slice: the kernel runs once per layer
    per step."""
    ev = kernel_events(ctx, signature)
    if not ev:
        return None, ev
    return len(ev) / ctx.cfg["num_hidden_layers"], ev


def step_programs(ctx, signature: str) -> list:
    """The device programs of the slice that ran the kernel ``signature``
    (see ``kernel_events``): each one batched decode step, as
    ``(name, start, end)``."""
    ev = kernel_events(ctx, signature)
    mods = sorted(ctx.trace.modules[0], key=lambda m: m[1]) if ev else []
    starts = [m[1] for m in mods]
    out = {}
    for _, s, e in ev:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and mods[i][2] >= e:
            out[i] = mods[i]
    return [out[i] for i in sorted(out)]
