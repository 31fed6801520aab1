"""Serving driver: deploy LLM functions on the full TIDAL stack and serve
a request stream end-to-end through the FaaS runtime.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch smollm-135m --functions 3 --requests 12 --lora

serves the architecture's published config (on a TPU the attention runs
the Pallas kernels).  ``--smoke`` swaps in the reduced config of the same
family for CPU rehearsals (``JAX_PLATFORMS=cpu``; kernels, where a test
pins them, run in interpret mode); ``--layers N`` cuts depth only.  The
first line names the platform and device the run is on.

Per request the runtime picks the service class itself: ``cold`` (first
invocation), ``fork`` (adaptive state forking from the template, prefill
overlapped with weight streaming) or ``warm`` (a kept-alive continuous-
batching engine — no forking at all).  Every TTFT feeds back into the
template's Eq. 1 residency budget.

``--tp N`` serves tensor-parallel over N devices; ``--instances K`` runs
K serving instances (one per mesh data-slice) with locality routing.
Params are created directly in their tensor-parallel layout.  Under
``--smoke`` the needed host devices are forced via XLA_FLAGS.

``--open-loop --qps Q [--deadline D]`` switches from the closed loop
(submit, wait, repeat) to an OPEN-loop Poisson driver over the async
gateway: requests are ticketed at their scheduled arrivals regardless of
how far behind the engines are, the gateway interleaves engines in
bounded quanta, and requests still queued past ``D`` seconds are shed
with a typed error.  This is the mode under which p95 TTFT is a
meaningful tail metric.
"""

from __future__ import annotations

import argparse
import collections
import os
import sys


def _flag_value(argv: list, flag: str, default: int) -> int:
    """Pre-argparse peek supporting both ``--flag N`` and ``--flag=N``;
    malformed values fall through to ``default`` (argparse reports them)."""
    for i, a in enumerate(argv):
        try:
            if a == flag and i + 1 < len(argv):
                return int(argv[i + 1])
            if a.startswith(flag + "="):
                return int(a.split("=", 1)[1])
        except ValueError:
            return default
    return default


def _force_host_devices_from_argv() -> None:
    """Set XLA_FLAGS before jax initializes a backend: a --smoke rehearsal
    of --tp/--instances needs tp*instances host devices."""
    if "--smoke" not in sys.argv:
        return
    n = (_flag_value(sys.argv, "--tp", 1)
         * _flag_value(sys.argv, "--instances", 1))
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1 and "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


if __name__ == "__main__":
    _force_host_devices_from_argv()

import jax
import numpy as np

from repro.core import api as tidal
from repro.data.pipeline import make_prompts
from repro.models.registry import get_config, get_model, get_smoke_model
from repro.runtime.faas import FaaSRuntime
from repro.runtime.gateway import DeadlineExceeded, InvocationRequest
from repro.utils import enable_compile_cache, fmt_bytes


def _serve_open_loop(rt: FaaSRuntime, model, args, rng) -> None:
    """Open-loop Poisson driver over the async gateway."""
    schedule, t = [], 0.0
    for r in range(args.requests):
        t += rng.exponential(1.0 / args.qps)
        name = f"fn-{rng.integers(args.functions)}"
        event = ({"adapter": f"adapter-{rng.integers(3)}"}
                 if args.lora else {})
        prompt = make_prompts(model.cfg.vocab_size, 1, args.prompt_len,
                              seed=100 + r)[0]
        schedule.append((t, InvocationRequest(
            name, prompt, event=event, max_new_tokens=args.max_new,
            deadline_s=args.deadline)))
    handles = rt.gateway.replay(schedule)

    ttfts, kinds = [], collections.Counter()
    for r, h in enumerate(handles):
        try:
            res = h.result()
        except DeadlineExceeded:
            kinds["shed"] += 1
            print(f"req{r:02d} {h.request.fn_name} SHED "
                  f"(deadline {args.deadline}s)")
            continue
        ttfts.append(res.ttft_s)
        kinds[res.kind] += 1
        print(f"req{r:02d} {res.fn_name} {res.kind:4s} "
              f"ttft={res.ttft_s*1e3:7.1f}ms e2e={res.e2e_s*1e3:7.1f}ms "
              f"tokens={[int(tk) for tk in res.tokens[:4]]}...")
    if ttfts:
        print(f"\nopen-loop @ {args.qps} qps: "
              f"p50 ttft {np.percentile(ttfts, 50)*1e3:.1f}ms  "
              f"p95 {np.percentile(ttfts, 95)*1e3:.1f}ms  "
              f"kinds={dict(kinds)}")
    if rt.control_plane is not None:
        cp = rt.control_plane
        print(f"control plane: {cp.stats}  "
              f"pinned={fmt_bytes(cp.pinned_nbytes())}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--functions", type=int, default=2)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4,
                    help="KV-cache slots per engine (decode batch capacity)")
    ap.add_argument("--keep-alive", type=float, default=60.0)
    ap.add_argument("--lora", action="store_true",
                    help="deploy dynamic (LoRA) function variants")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced config of the family (CPU "
                         "rehearsals) instead of the published one")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers (widths stay)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree per serving instance")
    ap.add_argument("--instances", type=int, default=1,
                    help="serving instances (mesh data-slices)")
    ap.add_argument("--open-loop", action="store_true",
                    help="Poisson arrivals through the async gateway "
                         "instead of the closed submit-wait loop")
    ap.add_argument("--qps", type=float, default=4.0,
                    help="open-loop arrival rate (requests/s)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="queueing deadline (s); expired requests shed")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="chunked prefill: split prompts into page-multiple "
                         "chunks interleaved with decode; the gateway "
                         "quantum becomes this token budget")
    ap.add_argument("--kv-dtype", choices=["int8"], default=None,
                    help="quantize the paged KV arena (int8 values + "
                         "per-row scales, dequantized inside the Pallas "
                         "decode kernel); default keeps the fp arena")
    ap.add_argument("--predictive", action="store_true",
                    help="attach the prewarm control plane: forecast "
                         "arrivals to pre-fork engines and adapt "
                         "keep-alive, and bake runtime-observed hot "
                         "prompt prefixes under a pinned-bytes budget")
    ap.add_argument("--prewarm-horizon", type=float, default=0.25,
                    help="forecast horizon (s) for predictive pre-forking")
    ap.add_argument("--prefix-budget", type=int, default=1 << 22,
                    help="pinned-bytes budget for runtime-learned "
                         "prefix KV")
    args = ap.parse_args()

    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"platform {dev.platform} ({dev.device_kind}) x "
          f"{jax.device_count()}, jax {jax.__version__}")
    mesh = None
    if args.tp > 1 or args.instances > 1:
        if jax.device_count() < args.tp * args.instances:
            raise SystemExit(
                f"need {args.tp * args.instances} devices, have "
                f"{jax.device_count()} (a CPU rehearsal takes --smoke, run "
                "as a script so XLA_FLAGS is forced before jax initializes)")
        mesh = jax.make_mesh((args.instances, args.tp), ("data", "model"))
        print(f"serving mesh: {args.instances} instance(s) x "
              f"{args.tp}-way tensor parallel")

    depth = {} if args.layers is None else {"n_layers": args.layers}
    model = (get_smoke_model(args.arch, **depth) if args.smoke
             else get_model(get_config(args.arch).replace(**depth)))
    cfg = model.cfg
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}, {cfg.dtype}")
    rt = FaaSRuntime(n_slots=args.slots,
                     max_len=args.prompt_len + args.max_new,
                     keep_alive_s=args.keep_alive,
                     trace_seq=args.prompt_len,
                     mesh=mesh,
                     chunk_tokens=args.chunk_tokens,
                     kv_dtype=args.kv_dtype)

    if args.predictive:
        from repro.runtime.controlplane import ControlPlane
        ControlPlane(rt, pinned_bytes_budget=args.prefix_budget,
                     prewarm_horizon_s=args.prewarm_horizon)
        print(f"control plane attached: prewarm horizon "
              f"{args.prewarm_horizon}s, prefix budget "
              f"{fmt_bytes(args.prefix_budget)}")

    rng = np.random.default_rng(0)
    plan = rt.instances[0].plan
    for i in range(args.functions):
        key = jax.random.PRNGKey(i)
        params = (model.init_params(key) if plan is None
                  else plan.init_params(model, key))
        name = f"fn-{i}"
        if args.lora:
            fn = tidal.lora_function(name, model, params,
                                     ["blocks.attn.wq"], n_adapters=3)
            rt.deploy(fn, {"adapter": "adapter-0"},
                      prewarm_seq=args.prompt_len)
        else:
            fn = tidal.static_function(name, model, params)
            rt.deploy(fn, {}, prewarm_seq=args.prompt_len)
    print(f"deployed {args.functions} function(s); pre-warmed "
          f"{rt.exe_cache.stats.misses} executables in "
          f"{rt.exe_cache.stats.compile_s:.1f}s")

    if args.open_loop:
        _serve_open_loop(rt, model, args, rng)
        return

    ttfts, kinds = [], collections.Counter()
    for r in range(args.requests):
        name = f"fn-{rng.integers(args.functions)}"
        event = ({"adapter": f"adapter-{rng.integers(3)}"}
                 if args.lora else {})
        prompt = make_prompts(model.cfg.vocab_size, 1, args.prompt_len,
                              seed=100 + r)[0]
        res = rt.submit(name, event, prompt, max_new_tokens=args.max_new)
        ttfts.append(res.ttft_s)
        kinds[res.kind] += 1
        fs = res.fork_stats
        detail = (f"reused={fmt_bytes(fs.reused_bytes):>10} "
                  f"streamed={fmt_bytes(fs.streamed_bytes):>10} "
                  f"dyn={fmt_bytes(fs.dynamic_bytes):>9}"
                  if fs is not None else " " * 43)
        print(f"req{r:02d} {name} "
              f"{'(' + event.get('adapter', '') + ')' if args.lora else '':14s}"
              f" {res.kind:4s} ttft={res.ttft_s*1e3:7.1f}ms "
              f"e2e={res.e2e_s*1e3:7.1f}ms {detail} "
              f"tokens={[int(t) for t in res.tokens[:4]]}...")

    print(f"\np50 ttft {np.percentile(ttfts, 50)*1e3:.1f}ms  "
          f"p95 {np.percentile(ttfts, 95)*1e3:.1f}ms  "
          f"kinds={dict(kinds)}  "
          f"(Eq.1-adapted residency: "
          f"{[fmt_bytes(t.resident_bytes) for t in rt.server.templates.values()]})")


if __name__ == "__main__":
    main()
