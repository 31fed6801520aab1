"""The serving program's own profiler spans in a trace, and the arithmetic
of the metrics that read them and its counters.

The program writes ``jax.profiler.TraceAnnotation`` spans named
``tidal.*`` on the host, with integer stats (``req_id``, ``layer``,
``live``, ...), on the same clock as the device planes.  ``load`` reads
them from an ``.xplane.pb``.  The readers in ``bench/metrics/`` find them
as ``ctx.trace.program`` (see ``read``) and the compile counter's change
over the window as ``ctx.counters``; they return None where a run has
neither (a program without the spans, or a harness that does not load
them).  All times are the profiler's nanoseconds.
"""

from __future__ import annotations

import dataclasses
import statistics

from benchlib import trace

PROGRAM_PREFIX = "tidal."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float
    stats: dict
    line: tuple          # (plane, line): one host thread


def load(path: str) -> list:
    """Every ``tidal.*`` host span of an ``.xplane.pb``, in start order."""
    from jax.profiler import ProfileData
    return spans(ProfileData.from_file(path))


def spans(pd) -> list:
    """Every ``tidal.*`` host span of a loaded ``ProfileData``, in start
    order."""
    out = []
    for i, plane in enumerate(pd.planes):
        if not plane.name.startswith("/host:"):
            continue
        for j, line in enumerate(plane.lines):
            out.extend(Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats), (i, j))
                       for e in line.events
                       if e.name.startswith(PROGRAM_PREFIX))
    return sorted(out, key=lambda s: s.start)


def read(ctx, metric):
    """``metric(spans, lo, hi)`` over the traced slice, or None where the
    run has no program spans to read."""
    spans = getattr(ctx.trace, "program", None) if ctx.trace else None
    if not spans:
        return None
    return metric(spans, *ctx.trace_ns)


def named(program: list, name: str) -> list:
    return [s for s in program if s.name == name]


def _inside(child: Span, parent: Span) -> bool:
    return (child.line == parent.line and parent.start <= child.start
            and child.end <= parent.end)


def decode_steps(program: list, lo: float, hi: float) -> list:
    """``(step, sync)`` span pairs of the batched decode steps whose
    ``tidal.decode.sync`` ends inside ``[lo, hi]``, in order."""
    syncs = named(program, "tidal.decode.sync")
    out = []
    for step in named(program, "tidal.step"):
        sync = next((s for s in syncs if _inside(s, step)), None)
        if sync is not None and lo <= sync.end <= hi:
            out.append((step, sync))
    return sorted(out, key=lambda p: p[1].end)


def decode_host_ms(program: list, lo: float, hi: float):
    """Median over the slice's decode steps of the host time between the
    previous step's sync and this one's, less this step's own sync: what a
    step costs the host beyond waiting for the device."""
    steps = decode_steps(program, lo, hi)
    host = [(b.end - a.end - (b.end - b.start)) * 1e-6
            for (_, a), (_, b) in zip(steps, steps[1:])]
    return statistics.median(host) if host else None


def live_row_share(program: list, lo: float, hi: float):
    """Percent of the decode rows the slice's steps computed that held a
    decoding request: sum of ``live`` over sum of ``rows``."""
    steps = [s for s, _ in decode_steps(program, lo, hi)]
    rows = sum(s.stats["rows"] for s in steps)
    if not rows:
        return None
    return 100.0 * sum(s.stats["live"] for s in steps) / rows


def weight_wait_ms(program: list, lo: float, hi: float):
    """Median over the forks that start inside ``[lo, hi]`` of the time
    their request spent blocked on weights still in flight (the summed
    ``tidal.stream.wait`` spans carrying its ``req_id``)."""
    forks = [s.stats["req_id"] for s in named(program, "tidal.fork")
             if lo <= s.start <= hi and s.stats.get("req_id", -1) >= 0]
    if not forks:
        return None
    waits: dict = {}
    for s in named(program, "tidal.stream.wait"):
        rid = s.stats.get("req_id")
        waits[rid] = waits.get(rid, 0.0) + (s.end - s.start) * 1e-6
    return statistics.median(waits.get(rid, 0.0) for rid in forks)


def compile_delta(before: dict | None, after: dict | None) -> dict | None:
    """The change of the program's compile counter
    (``{(event, fun_name): (count, seconds)}``) between two readings."""
    if before is None or after is None:
        return None
    out = {}
    for key, (n, s) in after.items():
        n0, s0 = before.get(key, (0, 0.0))
        if n > n0:
            out[key] = (n - n0, s - s0)
    return out


def innermost(program: list, s: float, e: float) -> Span | None:
    """The program span that overlaps ``[s, e]`` most; of spans that
    overlap it equally (a gap inside nested spans), the shortest."""
    best, key = None, None
    for p in program:
        ov = min(p.end, e) - max(p.start, s)
        if ov <= 0:
            continue
        k = (ov, -(p.end - p.start))
        if key is None or k > key:
            best, key = p, k
    return best


def label_gap(tr, program: list, modules, s: float, e: float) -> str:
    """An idle gap named by the innermost program span over it, and the
    device program that ran before it; without one, the benchmark's own
    label (``trace.label_gap``)."""
    p = innermost(program, s, e)
    if p is None:
        return trace.label_gap(tr, modules, s, e)
    before = [m for m in modules if m[2] <= s]
    prev = (trace.short_module(max(before, key=lambda m: m[2])[0])
            if before else "-")
    return f"{p.name} after {prev}"


def idle_by_span(tr, program: list, lo: float, hi: float) -> dict:
    """Seconds of the first device's idle time inside ``[lo, hi]`` by the
    innermost program span over each stretch of it (``None``: no span)."""
    out: dict = {}
    for gs, ge in trace.gaps(tr.ops[0] if tr.ops else [], lo, hi):
        cuts = sorted({gs, ge} | {t for p in program
                                  for t in (p.start, p.end) if gs < t < ge})
        for a, b in zip(cuts, cuts[1:]):
            p = innermost([q for q in program
                           if q.start <= a and q.end >= b], a, b)
            name = p.name if p is not None else None
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out
