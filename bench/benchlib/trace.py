"""Reduce a JAX profiler trace to device busy time, per-op time and idle
gaps labelled by what the host was doing.

Device operations are the events of each ``/device:TPU:<n>`` plane's
"XLA Ops" line; busy time is the union of their intervals.  Host spans are
the ``jax.profiler.TraceAnnotation`` events the benchmark writes around its
own calls into the runtime (names starting ``bench.``) and, apart, the
program's own ``tidal.*`` spans (``benchlib.program``), which name idle gaps
where they cover them.  All times are the profiler's nanoseconds.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
# ops that only hold other ops (a scan's loop): their time is their body's
CONTAINERS = ("while", "conditional", "call")
MODULES_LINE = "XLA Modules"
BENCH_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    ops: list        # per device: [(name, start, end)]
    modules: list    # per device: [(name, start, end)]
    host: list       # [(name, start, end)] benchmark spans
    program: list = dataclasses.field(default_factory=list)  # program.Span


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    from benchlib import program
    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            o, m = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    o = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events]
                elif line.name == MODULES_LINE:
                    m = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events]
            ops.append(o)
            modules.append(m)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(BENCH_PREFIX))
    return Trace(ops, modules, host, program.spans(pd))


def span(trace: Trace, name: str) -> tuple:
    """(start, end) of the first host span called ``name``."""
    for n, s, e in trace.host:
        if n == name:
            return s, e
    raise KeyError(f"no host span {name!r} in the trace")


def union(intervals, lo: float, hi: float) -> list:
    """Merged intervals, clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union([(s, e) for _, s, e in ops], lo, hi))


def gaps(ops, lo: float, hi: float) -> list:
    """Idle intervals of one device inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in union([(s, e) for _, s, e in ops], lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


_SHORT = re.compile(r"^%?([^\s=]+?)(\.\d+)?\s*=")


def short_name(name: str) -> str:
    """``'%fusion.12 = bf16[..] fusion(..)'`` -> ``'fusion'``."""
    m = _SHORT.match(name)
    return m.group(1) if m else name.split(" ")[0]


def op_kind(name: str) -> str:
    """The HLO opcode of an op event (``custom-call``, ``fusion``, ...)."""
    m = re.search(r"\}?\s([a-z][a-z0-9_-]*)\(", name)
    return m.group(1) if m else ""


def op_seconds(ops, lo: float, hi: float) -> dict:
    """Seconds per short op name inside ``[lo, hi]``, with an example of
    the full name; loop and call ops, which only hold others, are left
    out."""
    out: dict = {}
    for name, s, e in ops:
        if op_kind(name) in CONTAINERS:
            continue
        d = min(e, hi) - max(s, lo)
        if d <= 0:
            continue
        key = short_name(name)
        t, ex = out.get(key, (0.0, name))
        out[key] = (t + d * 1e-9, ex)
    return out


def matching_text(ops, pattern: str, lo: float, hi: float) -> list:
    """Op events inside ``[lo, hi]`` whose whole HLO text (result shape,
    opcode, operands) matches the regular expression ``pattern``."""
    rx = re.compile(pattern)
    return [(n, s, e) for n, s, e in ops
            if s >= lo and e <= hi and rx.search(n)]


def label_gap(trace: Trace, modules, s: float, e: float) -> str:
    """The benchmark span that overlaps an idle gap most, and the device
    program that ran just before it."""
    best, best_ov = "no bench span", 0.0
    for n, hs, he in trace.host:
        ov = min(he, e) - max(hs, s)
        if ov > best_ov and n != "bench.window":
            best, best_ov = n, ov
    before = [m for m in modules if m[2] <= s]
    prev = short_module(max(before, key=lambda m: m[2])[0]) if before else "-"
    return f"{best} after {prev}"


def short_module(name: str) -> str:
    """``'jit_step(1573..)'`` -> ``'jit_step'``."""
    return name.split("(")[0]


def reduce(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """Busy seconds (mean over devices), window seconds, the device ops
    that took most time and the longest idle gaps, all inside ``[lo, hi]``;
    a gap is named by the innermost program span over it, else by the
    benchmark's (``program.label_gap``)."""
    from benchlib import program
    n_dev = max(1, len(trace.ops))
    busy = sum(busy_ns(o, lo, hi) for o in trace.ops) / n_dev * 1e-9
    ops = op_seconds(trace.ops[0] if trace.ops else [], lo, hi)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][0])[:top]
    g = gaps(trace.ops[0], lo, hi) if trace.ops else [(lo, hi)]
    g = sorted(g, key=lambda ab: ab[0] - ab[1])[:top]
    mods = trace.modules[0] if trace.modules else []
    return {
        "busy_s": busy,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[f"{k} ({op_kind(ex)})", t] for k, (t, ex) in top_ops],
        "idle_gaps": [[program.label_gap(trace, trace.program, mods, s, e),
                       (e - s) * 1e-9] for s, e in g],
    }
