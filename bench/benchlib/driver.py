"""Drive one cell through the program's serving runtime: deploy, warm up,
measure one window, and keep a record of every request.

The program gets only generated inputs: weights from ``weights``, prompts
and arrivals from ``traffic``.  The open loop copies
``InvocationGateway.replay`` (due times, pumping between arrivals, arrivals
backdated to their due time) and clocks each ``submit`` call itself; in
the closed loop each of the mix's callers sends its next request when its
last one finished.  ``jax.profiler.TraceAnnotation`` spans named ``bench.*`` mark the
benchmark's own calls into the runtime.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import time

import jax
import numpy as np

from benchlib import traffic, weights

# a request still unfinished this long after the window closed has failed
DRAIN_S = 60.0
TRACE_DIR = ".bench_out/traces"


def model_config(c: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.models.config import ModelConfig
    return ModelConfig(**weights.family(c).model_config(c))


class CacheCounter:
    """Persistent compile-cache hits and misses, from JAX's events."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@dataclasses.dataclass
class Slice:
    """The traced part of a window, on the window's clock."""
    start: float
    seconds: float
    log_dir: str
    began: float | None = None
    ended: float | None = None
    _span: object = None

    def poll(self, t: float) -> None:
        if self.began is None and t >= self.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self.began = t
        elif (self.began is not None and self.ended is None
              and t >= self.began + self.seconds):
            self.stop(t)

    def stop(self, t: float) -> None:
        if self.began is None or self.ended is not None:
            return
        self._span.__exit__(None, None, None)
        self.ended = t
        jax.profiler.stop_trace()

    def next_event(self) -> float:
        if self.began is None:
            return self.start
        if self.ended is None:
            return self.began + self.seconds
        return float("inf")


class Runtime:
    """The program under test, deployed with one cell's functions."""

    def __init__(self, cfg: dict, cell: dict, mix: dict, seed: int,
                 log=print):
        from repro.core import api as tidal
        from repro.models.registry import get_model
        from repro.runtime.faas import FaaSRuntime

        self.cfg, self.cell, self.mix, self.seed = cfg, cell, mix, seed
        self.model = get_model(model_config(cfg))
        kw = {"n_slots": cell["n_slots"], "max_len": cell["max_len"]}
        if cell.get("prewarm") is not None:
            kw["prewarm"] = bool(cell["prewarm"])
        if cell.get("keep_alive_s") is not None:
            kw["keep_alive_s"] = float(cell["keep_alive_s"])
        self.rt = FaaSRuntime(**kw)
        if cell.get("template_device_budget_bytes") is not None:
            # the runtime's own template server, with the cell's budget
            self.rt.server.device_budget = int(
                cell["template_device_budget_bytes"])
        vocab = cfg["vocab_size"]
        self.templates = traffic.templates(mix, cell["functions"], vocab,
                                           seed)
        for f in range(cell["functions"]):
            t = time.perf_counter()
            params = weights.served_params(cfg, seed, f)
            fn = tidal.static_function(self.fn_name(f), self.model, params)
            del params
            tpl = self.templates[f] if len(self.templates[f]) else None
            self.rt.deploy(fn, {}, template_prompt=tpl)
            log(f"deployed {self.fn_name(f)} in "
                f"{time.perf_counter() - t:.1f}s")

    @staticmethod
    def fn_name(f: int) -> str:
        return f"fn{f}"

    def request(self, r: traffic.Request, arrival_s: float | None):
        from repro.runtime.gateway import InvocationRequest
        return InvocationRequest(fn_name=self.fn_name(r.fn), prompt=r.prompt,
                                 max_new_tokens=r.max_new,
                                 arrival_s=arrival_s)

    def warm_up(self, lengths: list, log=print) -> None:
        """Run, through the gateway as the window will, every shape the
        window uses: each unshared prompt length in ``lengths``, spread
        over the functions.  Where engines outlive a request (keep-alive
        above zero), each function is first forked once with the shortest
        prompt and touched again at the end, so the window finds every
        engine warm; where they scale to zero, every warm-up request
        forks, as every request of the window will."""
        lens = sorted(lengths)
        n_fn = self.cell["functions"]
        out_lens = traffic.levels(self.mix["output_tokens"])
        max_new = 2 if len(out_lens) > 1 else out_lens[0]
        stays_warm = self.cell.get("keep_alive_s") != 0
        plan = [(i % n_fn, lens[i % len(lens)])
                for i in range(max(len(lens), n_fn))]
        if stays_warm:
            first = [(f, lens[0]) for f in range(n_fn)]
            plan = first + plan + first
        vocab = self.cfg["vocab_size"]
        rng = traffic.rng_for(self.seed, 9)
        gw = self.rt.gateway
        for i, (f, n) in enumerate(plan):
            tail = rng.integers(0, vocab, n).astype(np.int32)
            req = traffic.Request(f, np.concatenate([self.templates[f], tail]),
                                  max_new)
            t = time.perf_counter()
            h = gw.submit(self.request(req, None))
            res = h.result()
            gw.pump()
            del h
            log(f"warm-up {i + 1}/{len(plan)}: fn{f} prompt {len(req.prompt)} "
                f"{res.kind} {time.perf_counter() - t:.2f}s")
        gc.collect()


def _record(r: traffic.Request, due: float, submit_at: float, h) -> dict:
    rec = {"fn": r.fn, "prompt": r.prompt, "prompt_len": len(r.prompt),
           "max_new": r.max_new, "due": due, "submit_at": submit_at,
           "status": "unfinished", "ttft_s": float("nan"),
           "e2e_s": float("nan"), "n_out": 0, "tokens": np.zeros(0, np.int32),
           "reused": 0, "kind": "", "reused_bytes": 0, "streamed_bytes": 0,
           "token_times_s": None}
    if not h.done:
        return rec
    try:
        res = h.result(timeout=0)
    except Exception as e:  # noqa: BLE001 — any failure is the request's
        rec["status"] = f"failed: {type(e).__name__}"
        return rec
    rec.update(status=res.status, ttft_s=float(res.ttft_s),
               e2e_s=float(res.e2e_s), n_out=len(res.tokens),
               tokens=np.asarray(res.tokens, np.int32),
               reused=int(res.reused_prefix_len), kind=res.kind,
               token_times_s=getattr(res, "token_times_s", None))
    if res.fork_stats is not None:
        rec["reused_bytes"] = int(res.fork_stats.reused_bytes)
        rec["streamed_bytes"] = int(res.fork_stats.streamed_bytes)
    return rec


def open_loop(run: Runtime, schedule: list, seconds: float,
              tslice: Slice | None, drain_s: float = DRAIN_S) -> tuple:
    """Offer ``schedule`` on its due times; returns (records, t0)."""
    gw = run.rt.gateway
    live: list = []
    t0 = time.perf_counter()

    def now():
        t = time.perf_counter() - t0
        if tslice is not None:
            tslice.poll(t)
        return t

    def wait_until(t_due: float) -> None:
        while True:
            t = now()
            nxt = min(t_due, tslice.next_event() if tslice else t_due)
            if t >= t_due:
                return
            wait = max(nxt - t, 1e-4)
            if any(not h.done for _, _, _, h in live):
                with jax.profiler.TraceAnnotation("bench.pump"):
                    gw.pump(timeout=wait)
            else:
                with jax.profiler.TraceAnnotation("bench.idle"):
                    time.sleep(wait)

    for r in schedule:
        wait_until(r.due)
        submit_at = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation("bench.submit"):
            h = gw.submit(run.request(r, t0 + r.due))
        live.append((r, r.due, submit_at, h))
    wait_until(seconds)
    if tslice is not None:
        tslice.stop(now())
    with jax.profiler.TraceAnnotation("bench.drain"):
        gw.pump(timeout=drain_s)
    return [_record(r, d, s, h) for r, d, s, h in live], t0


def closed_loop(run: Runtime, sequence: list, seconds: float,
                tslice: Slice | None) -> tuple:
    """``callers`` (the mix's, default 1) concurrent callers: each sends
    its next request when its last one finished, taking requests in order
    from the one ``sequence``, until the window closes; the requests in
    flight then are waited for."""
    gw = run.rt.gateway
    callers = int(run.mix.get("callers", 1))
    todo = iter(sequence)
    live, records = [], []      # (index, request, handle) in flight
    t0 = time.perf_counter()
    deadline = t0 + seconds + DRAIN_S

    def finished() -> bool:
        return any(h.done for _, _, h in live)

    while True:
        t = time.perf_counter() - t0
        while len(live) < callers and t < seconds:
            r = next(todo, None)
            if r is None:
                break
            if tslice is not None:
                tslice.poll(t)
            with jax.profiler.TraceAnnotation("bench.submit"):
                live.append((len(records), r,
                             gw.submit(run.request(r, None))))
            records.append(None)
        if not live:
            break
        while not finished() and time.perf_counter() < deadline:
            step = 0.05 if tslice is None else max(
                1e-3, tslice.next_event() - (time.perf_counter() - t0))
            with jax.profiler.TraceAnnotation("bench.wait"):
                gw.pump(until=finished, timeout=min(step, 1.0))
            if tslice is not None:
                tslice.poll(time.perf_counter() - t0)
        live = _collect(live, records, t0,
                        late=time.perf_counter() >= deadline)
        # the gateway keeps finished handles, and through them their
        # engines' weights, until a pump drops them: drop them before the
        # next request forks
        gw.pump(timeout=0)
    if tslice is not None:
        tslice.stop(time.perf_counter() - t0)
    return records, t0


def _collect(live: list, records: list, t0: float, late: bool) -> list:
    """Record the finished requests in flight (all of them when ``late``)
    and return the rest; no finished handle outlives this call."""
    still = []
    for i, r, h in live:
        if h.done or late:
            due = h.submit_s - t0
            records[i] = _record(r, due, due, h)
        else:
            still.append((i, r, h))
    return still


def memory_peak_bytes(devices) -> int:
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def trace_dir(workload: str, seed: int) -> str:
    return os.path.join(TRACE_DIR, f"{workload}.{seed}")
