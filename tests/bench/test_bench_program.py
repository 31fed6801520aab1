"""Readers of the program's own spans and counters: the five per-layer
quantities, gap labels by the innermost program span, and silence (None,
no error) on a run that has neither."""

import pathlib

import numpy as np
import pytest

import _benchpath  # noqa: F401
from benchlib import harness, peaks, program, spec, trace

DATA = pathlib.Path(__file__).parent / "data" / "paged_decode_v5e.xplane.pb"
MAIN, STREAMER = (1, 0), (1, 1)
MS = 1_000_000                       # profiler ns per ms


def _span(name, start, end, line=MAIN, **stats):
    return program.Span(name, start * MS, end * MS, stats, line)


def _decode_step(t, sync_ms, live=1, rows=4):
    """A decode step at ``t`` ms whose sync ends at ``t + 10``."""
    return [_span("tidal.step", t, t + 10, live=live, rows=rows, admitted=0,
                  chunked=0),
            _span("tidal.decode.dispatch", t + 1, t + 2),
            _span("tidal.decode.sync", t + 10 - sync_ms, t + 10),
            _span("tidal.emit", t + 10, t + 10)]


def _fork(rid, t, waits):
    """A fork of request ``rid`` at ``t`` ms, its prefill blocked for each
    of ``waits`` ms, its uploads on the streamer thread."""
    out = [_span("tidal.submit", t, t + 5, req_id=rid),
           _span("tidal.fork", t + 1, t + 4, req_id=rid, streamed_bytes=30)]
    at = t + 10
    for layer, w in enumerate(waits):
        out += [_span("tidal.stream.upload", at, at + w, STREAMER,
                      req_id=rid, layer=layer, nbytes=10),
                _span("tidal.stream.wait", at, at + w, req_id=rid,
                      layer=layer)]
        at += w + 1
    return out


def _program():
    # steps every 20 ms (10 ms on the host between syncs), one of them
    # with two live rows; request 7 forks inside the slice, 3 before it
    spans = _fork(3, -100, [50, 50]) + _fork(7, 0, [100, 300, 0])
    for i, t in enumerate(range(500, 600, 20)):
        spans += _decode_step(t, sync_ms=6, live=2 if i == 2 else 1)
    return sorted(spans, key=lambda s: s.start)


def _ctx(records=(), program_spans=None, counters=None):
    t = trace.Trace([[]], [[]], [])
    if program_spans is not None:
        t.program = program_spans
    ctx = harness.Ctx(records=list(records), window_s=10.0, setup_s=0.0,
                      cfg=spec.config("qwen3-14b-l10"), cell={"n_slots": 4},
                      peak=peaks.peaks_for("TPU v5 lite"), runtime={},
                      trace=t, trace_ns=(0, 1000 * MS), slice_s=(0.0, 1.0))
    if counters is not None:
        ctx.counters = counters
    return ctx


def _read(name, ctx):
    return spec.metric_reader(name).read(ctx)


def test_decode_host_time_is_the_time_between_syncs_less_the_sync():
    # sync ends 20 ms apart, each sync 6 ms long: 14 ms on the host
    assert _read("decode_host_ms.cold", _ctx(program_spans=_program())) == (
        pytest.approx(14.0))


def test_live_row_share_counts_live_rows_of_decode_steps():
    # five steps of four rows, 1 + 1 + 2 + 1 + 1 live
    assert _read("live_row_share.cold", _ctx(program_spans=_program())) == (
        pytest.approx(100 * 6 / 20))


def test_weight_wait_sums_one_requests_waits_over_forks_in_the_slice():
    # request 3 forked before the slice: only request 7's 400 ms count
    assert _read("weight_wait_ms.cold", _ctx(program_spans=_program())) == (
        pytest.approx(400.0))


def test_compile_time_per_fork_reads_the_counter_delta():
    before = {("/jax/core/compile/jaxpr_trace_duration", "f"): (1, 0.5)}
    after = {("/jax/core/compile/jaxpr_trace_duration", "f"): (3, 0.9),
             ("/jax/core/compile/backend_compile_duration", "jit(f)"):
             (2, 0.2)}
    delta = program.compile_delta(before, after)
    assert delta == {
        ("/jax/core/compile/jaxpr_trace_duration", "f"): (2, pytest.approx(
            0.4)),
        ("/jax/core/compile/backend_compile_duration", "jit(f)"): (2, 0.2)}
    recs = [{"kind": "fork"}, {"kind": "cold"}, {"kind": "warm"}]
    assert _read("compile_ms_per_fork.cold", _ctx(recs, counters=delta)) == (
        pytest.approx(300.0))
    assert program.compile_delta(None, after) is None


def test_inter_token_latency_reads_the_token_stamps():
    def rec(times, status="done"):
        return {"status": status, "token_times_s": np.asarray(times)}

    recs = [rec([0.0, 0.01, 0.02, 0.03]),      # gaps of 10 ms
            rec([1.0, 1.05]),                   # one gap of 50 ms
            rec([2.0, 9.0], status="cancelled")]
    want = np.percentile([10, 10, 10, 50], 95)
    assert _read("itl_p95_ms.cold", _ctx(recs)) == pytest.approx(want)


NEW = ["decode_host_ms.cold", "live_row_share.cold", "weight_wait_ms.cold",
       "compile_ms_per_fork.cold", "itl_p95_ms.cold"]


@pytest.mark.parametrize("name", NEW)
def test_readers_are_silent_where_the_program_has_no_spans(name):
    """A program without the spans, counters and stamps (or a harness that
    does not pass them on) reads None, untraced runs included."""
    recs = [{"status": "done", "kind": "fork", "ttft_s": 1.0, "e2e_s": 2.0,
             "n_out": 3, "due": 0.0, "prompt_len": 10}]
    assert _read(name, _ctx(recs)) is None
    ctx = _ctx(recs)
    ctx.trace = None
    assert _read(name, ctx) is None


def test_gap_labels_prefer_the_innermost_program_span():
    ops = [("%a = f32[] fusion()", 0, 10), ("%b = f32[] fusion()", 90, 100)]
    t = trace.Trace([ops], [[("jit_serve_decode(1)", 0, 10)]],
                    [("bench.window", 0, 100), ("bench.wait", 5, 95)])
    nested = [program.Span("tidal.step", 5, 95, {}, MAIN),
              program.Span("tidal.prefill", 8, 92, {}, MAIN),
              program.Span("tidal.stream.wait", 10, 90, {}, MAIN),
              program.Span("tidal.stream.upload", 12, 60, {}, STREAMER)]
    label = program.label_gap(t, nested, t.modules[0], 10, 90)
    assert label == "tidal.stream.wait after jit_serve_decode"
    # a gap half covered by an inner span goes to the span over all of it
    assert program.label_gap(t, nested[:2], t.modules[0], 10, 90) == (
        "tidal.prefill after jit_serve_decode")
    # with no program span over it, the benchmark's own label
    assert program.label_gap(t, [], t.modules[0], 10, 90) == (
        "bench.wait after jit_serve_decode")
    # idle time by stretch: the upload is the innermost span where it runs
    by = program.idle_by_span(t, nested, 0, 100)
    assert by == {"tidal.stream.wait": pytest.approx(32e-9),
                  "tidal.stream.upload": pytest.approx(48e-9)}
    by = program.idle_by_span(t, nested[3:], 0, 100)
    assert by == {None: pytest.approx(32e-9),
                  "tidal.stream.upload": pytest.approx(48e-9)}


def test_recorded_trace_has_no_program_spans_and_reads_as_before():
    t = trace.load(str(DATA))
    assert program.load(str(DATA)) == []
    assert t.program == []               # loaded with the trace, none here
    lo = min(s for _, s, _ in t.ops[0])
    hi = max(e for _, _, e in t.ops[0])
    red = trace.reduce(t, lo, hi)
    idle = sum(s for _, s in red["idle_gaps"])
    by = program.idle_by_span(t, [], lo, hi)
    assert set(by) <= {None}
    assert sum(by.values()) >= idle * (1 - 1e-9)
