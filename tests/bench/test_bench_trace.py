"""Trace reduction: busy union, idle gaps, kernel time and roofline share,
on synthetic events and on a small trace recorded on a TPU v5e."""

import pathlib

import pytest

import _benchpath  # noqa: F401
from benchlib import cost, peaks, spec, trace

DATA = pathlib.Path(__file__).parent / "data" / "paged_decode_v5e.xplane.pb"


def _ops(*spans):
    return [(f"%op.{i} = f32[] fusion(f32[] %x)", s, e)
            for i, (s, e) in enumerate(spans)]


def test_busy_is_the_union_of_overlapping_ops_clipped_to_the_window():
    ops = _ops((0, 10), (5, 20), (30, 40), (35, 38), (90, 120))
    assert trace.busy_ns(ops, 0, 100) == 20 + 10 + 10
    assert trace.busy_ns(ops, 8, 32) == 12 + 2


def test_gaps_are_the_complement_of_busy_time():
    ops = _ops((10, 20), (15, 25), (40, 50))
    assert trace.gaps(ops, 0, 60) == [(0, 10), (25, 40), (50, 60)]
    assert trace.gaps([], 0, 5) == [(0, 5)]


def test_short_names_and_opcodes():
    name = ("%copy.4 = bf16[8193,8,3,64]{3,1,2,0:T(8,128)(2,1)S(1)} "
            "copy(bf16[8193,8,3,64]{0,3,2,1:T(8,128)(2,1)} %kp.1)")
    assert trace.short_name(name) == "copy"
    assert trace.op_kind(name) == "copy"
    assert trace.short_name("%fusion = f32[] fusion(f32[] %a)") == "fusion"


def test_gap_labels_name_the_benchmark_span_and_the_program_before_it():
    t = trace.Trace(ops=[_ops((0, 10), (50, 60))],
                    modules=[[("jit_decode(123)", 0, 10)]],
                    host=[("bench.window", 0, 60), ("bench.pump", 5, 45)])
    red = trace.reduce(t, 0, 60)
    assert red["busy_s"] == pytest.approx(20e-9)
    assert red["idle_gaps"][0] == ["bench.pump after jit_decode",
                                   pytest.approx(40e-9)]


def test_recorded_trace_kernel_time_and_roofline():
    t = trace.load(str(DATA))
    assert len(t.ops) == 1 and len(t.modules[0]) == 5
    lo = min(s for _, s, _ in t.ops[0])
    hi = max(e for _, _, e in t.ops[0])
    red = trace.reduce(t, lo, hi)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["device_ops"][0][0] == "bench_attn (custom-call)"
    kernel = spec.metric_reader("paged_decode_roofline.chat").KERNEL
    ev = trace.matching_text(t.ops[0], kernel.format(rows=32), lo, hi)
    assert len(ev) == 5                 # one kernel call per step
    assert trace.matching_text(t.ops[0], kernel.format(rows=4), lo, hi) == []
    secs = sum(e - s for _, s, e in ev) * 1e-9
    assert secs == pytest.approx(0.02328403)
    # the recorded call: 32 rows, lengths 1..2000, every page mapped
    cfg = {"hidden_size": 576, "num_attention_heads": 9,
           "num_key_value_heads": 3, "head_dim": 64,
           "intermediate_size": 1536, "vocab_size": 49152,
           "num_hidden_layers": 1, "tie_word_embeddings": True,
           "family": "dense"}
    import numpy as np
    lengths = np.linspace(1, 2000, 32).astype(np.int32)
    work = cost.paged_decode(cfg, list(np.tile(lengths - 1, 5)), 5, 32, 8)
    share, bound = cost.roofline_share(work, secs,
                                       peaks.peaks_for("TPU v5 lite"))
    assert bound == "hbm"
    assert 0 < share < 100


def test_decode_steps_are_the_programs_that_run_the_kernel():
    from benchlib import harness, readers, weights
    kernel = spec.metric_reader("decode_mfu.cold").KERNEL
    call = ("%closed_call.3 = bf16[4,8,5,128]{3,2,1,0} "
            "custom-call(s32[4,320]{1,0} %pt, bf16[4,8,5,128]{3,2,1,0} %q)")
    other = "%fusion.1 = bf16[4,5120]{1,0} fusion(bf16[4,5120]{1,0} %x)"
    ops = [(other, 0, 5), (call, 10, 20), (call, 30, 40),
           (other, 60, 70), (call, 110, 120)]
    mods = [("jit__lambda(1)", 8, 50), ("jit_block_fn(2)", 55, 75),
            ("jit__lambda(1)", 100, 130)]
    cfg = spec.config("qwen3-14b-l10")
    ctx = harness.Ctx(
        records=[{"due": 0.0, "ttft_s": 1.0, "e2e_s": 2.0, "n_out": 3,
                  "prompt_len": 100, "status": "done"}],
        window_s=10.0, setup_s=0.0, cfg=cfg, cell={"n_slots": 4},
        peak=peaks.peaks_for("TPU v5 lite"), runtime={},
        trace=trace.Trace([ops], [mods], []), trace_ns=(0, 200),
        slice_s=(0.0, 10.0))
    steps = readers.step_programs(ctx, kernel)
    assert [(s, e) for _, s, e in steps] == [(8, 50), (100, 130)]
    # two decode tokens (positions 100 and 101) over 72 ns of steps
    flops = cost.decode_token_flops(cfg, [100, 101])
    assert flops > 2 * 2 * weights.n_params(cfg)["active"]
    want = 100 * flops / 72e-9 / peaks.peaks_for("TPU v5 lite")["bf16_flops"]
    assert spec.metric_reader("decode_mfu.cold").read(ctx) == pytest.approx(
        want)
    ctx.trace = trace.Trace([[(other, 0, 5)]], [mods], [])
    assert spec.metric_reader("decode_mfu.cold").read(ctx) is None
