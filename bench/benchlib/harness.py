"""One run of one cell: set up, measure one window, read the metrics,
check the served tokens against the reference."""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import time

import jax

from benchlib import (check, driver, peaks, program, spec, traffic, trace,
                      weights)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Ctx:
    """What a metric reader may read."""
    records: list            # one dict per request of the window
    window_s: float          # length of the measured window
    setup_s: float
    cfg: dict                # the configuration file
    cell: dict               # the cell file
    peak: dict               # the device's published peaks
    runtime: dict            # serving settings the program chose
    trace: object = None     # trace.Trace of the traced slice, or None
    trace_ns: tuple = None   # the slice on the trace's clock
    slice_s: tuple = None    # the slice on the window's clock
    counters: dict = None    # the program's compile counter over the window


def host_memory() -> str:
    """This process's resident and swapped-out host memory (Linux)."""
    try:
        with open("/proc/self/status") as f:
            rows = dict(line.split(":", 1) for line in f if ":" in line)
    except OSError:
        return "unknown"
    return ", ".join(f"{k} {rows[k].strip()}" for k in ("VmHWM", "VmRSS",
                                                         "VmSwap")
                     if k in rows)


def device_memory(devices) -> str:
    """Bytes in use, the peak, and the largest free block on each chip."""
    keys = ("bytes_in_use", "peak_bytes_in_use", "largest_free_block_bytes",
            "bytes_limit")
    return "; ".join(", ".join(f"{k} {d.memory_stats().get(k)}" for k in keys)
                     for d in devices)


def rehearsal(cfg: dict, cell: dict, mix: dict) -> tuple:
    """Tiny stand-ins of a cell's files for a run on the CPU: every path of
    the harness, nothing of the chip's sizes.  The configuration's family
    gives its own tiny sizes; the callers are capped at the slots."""
    cfg = weights.family(cfg).rehearsal(cfg)
    # the gap limit at these sizes, set from CPU readings over 19 seeds
    # of each cell: sound runs 0-0.0327, the float8 control 0.0977-0.760
    cell = dict(cell, functions=min(cell["functions"], 2), n_slots=4,
                max_len=128, template_device_budget_bytes=None,
                rate_per_s=2.0, check=dict(cell["check"], max_logit_gap=0.06))
    mix = dict(mix, shared_prefix_tokens=min(
        mix.get("shared_prefix_tokens", 0), 16),
        callers=min(int(mix.get("callers", 1)), cell["n_slots"]))
    mix["prompt_tokens"] = {"dist": "lognormal", "median": 16, "sigma": 0.5,
                            "min": 8, "max": 32, "levels": 2}
    out = mix["output_tokens"]
    mix["output_tokens"] = ({"fixed": min(out["fixed"], 4)} if "fixed" in out
                            else {"dist": "lognormal", "median": 4,
                                  "sigma": 0.5, "min": 2, "max": 6,
                                  "levels": 2})
    return cfg, cell, mix


def read_metrics(ctx: Ctx, entries: list) -> dict:
    out = {}
    for m in entries:
        v = spec.metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(name: str, seed: int, seconds: float, traced: bool, t_start: float,
        *, rehearse: bool = False, control: bool = False,
        keep_trace: str | None = None) -> dict:
    """Run cell ``name`` once; returns the result line's object."""
    from repro.utils import enable_compile_cache

    bench = spec.benchmark()
    w = spec.workload(name, bench)
    cell, cfg, mix = spec.cell(name), spec.config(w["config"]), spec.mix(
        w["traffic"])
    if rehearse:
        cfg, cell, mix = rehearsal(cfg, cell, mix)
    devices = jax.devices()[: w["chips"]]
    peak = peaks.peaks_for("TPU v5 lite" if rehearse
                           else devices[0].device_kind)
    cache = enable_compile_cache()
    if cache is not None:
        # the checkout's own directory, whatever the environment names:
        # a later run of this checkout finds what the first one compiled
        cache = str(spec.ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    log(f"cache: {cache or 'off (CPU backend)'}")
    counter = driver.CacheCounter()

    vocab = cfg["vocab_size"]
    if mix["loop"] == "open":
        reqs = traffic.open_loop(mix, cell["functions"], vocab,
                                 cell["rate_per_s"], seconds, seed)
    else:
        reqs = traffic.closed_loop(mix, cell["functions"], vocab, seed)
    run_ = driver.Runtime(cfg, cell, mix, seed, log=log)
    run_.warm_up(traffic.unshared_lengths(mix, reqs), log=log)
    if not rehearse:
        log(f"device memory after warm-up: {device_memory(devices)}")
    hits0, misses0 = counter.hits, counter.misses
    compiles0 = run_.rt.stats().get("compile")

    tslice = None
    if traced:
        tr = cell["trace"]
        tslice = driver.Slice(min(tr["start_s"], seconds / 3),
                              min(tr["seconds"], seconds / 2),
                              keep_trace or driver.trace_dir(name, seed))
    loop = driver.open_loop if mix["loop"] == "open" else driver.closed_loop
    try:
        records, t0 = loop(run_, reqs, seconds, tslice)
    except Exception:
        # what the window had compiled and held when it stopped
        log(f"window stopped: compile cache {counter.hits - hits0} hits, "
            f"{counter.misses - misses0} misses; program compiles "
            f"{program.compile_delta(compiles0, run_.rt.stats().get('compile'))}")
        if not rehearse:
            log(f"device memory when the window stopped: "
                f"{device_memory(devices)}")
        raise
    setup_s = t0 - t_start
    compiles = program.compile_delta(compiles0,
                                     run_.rt.stats().get("compile"))
    log(f"window: {len(records)} requests; compile cache in the window: "
        f"{counter.hits - hits0} hits, {counter.misses - misses0} misses")
    log(f"host memory: {host_memory()}")
    for i, r in enumerate(records):
        log(f"request {i}: fn{r['fn']} {r['kind'] or r['status']} prompt "
            f"{r['prompt_len']} out {r['n_out']} due {r['due']:.3f}s ttft "
            f"{r['ttft_s'] * 1e3:.1f}ms e2e {r['e2e_s'] * 1e3:.1f}ms "
            f"streamed {r['streamed_bytes'] / 1e9:.2f}GB")
    mem_peak = (driver.memory_peak_bytes(devices) if not rehearse else 0)
    rt = run_.rt
    runtime = {"page_size": rt.page_size,
               "kv_itemsize": 1 if rt.kv_dtype == "int8" else 2}
    del run_, rt
    gc.collect()

    ctx = Ctx(records, float(seconds), setup_s, cfg, cell, peak, runtime,
              counters=compiles)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    breakdown = None
    if tslice is not None and tslice.began is not None:
        tr_ = trace.load(trace.find_xplane(tslice.log_dir))
        lo, hi = trace.span(tr_, "bench.window")
        ctx.trace, ctx.trace_ns = tr_, (lo, hi)
        ctx.slice_s = (tslice.began, tslice.ended)
        red = trace.reduce(tr_, lo, hi)
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        if keep_trace is None:
            shutil.rmtree(tslice.log_dir, ignore_errors=True)
    metrics = read_metrics(ctx, spec.metrics_for(name, traced, bench))

    lim = cell["check"]
    picked = check.sample(records, seed, lim["min_tokens"],
                          lim["max_requests"])
    result_check = {}
    correct = False
    if picked:
        t = time.perf_counter()
        g = check.gaps(spec.reference(cfg["reference"]), cfg, seed, picked,
                       control=control)
        log(f"reference: {g['requests']} requests, {g['tokens']} tokens in "
            f"{time.perf_counter() - t:.1f}s")
        # with ``control`` the float8 reference stands in the program's
        # place: its gap is the number compared, and it has to fail
        key = "control_max_logit_gap" if control else "max_logit_gap"
        if control:
            result_check["served_max_logit_gap"] = {
                "value": g["max_logit_gap"], "limit": lim["max_logit_gap"]}
        result_check[key] = {"value": g[key], "limit": lim["max_logit_gap"]}
        correct = g[key] <= lim["max_logit_gap"]
    failed = sum(r["status"] != "done" for r in records)
    out = {"correct": bool(correct), "attempted": len(records),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = result_check
    return out
