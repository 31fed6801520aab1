"""Bring-up check: the serving path at published width on a TPU.

    python chip_smoke.py          # one chip: smollm-135m, published config
    python chip_smoke.py --tp4    # four chips: llama2-13b, 4-way tensor parallel
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal [--tp4]

Every phase runs in this one process (a chip belongs to one process) and
the first phase that fails ends the run with a non-zero exit.  On one chip:

  1. device   — JAX's first device is a TPU; there is no CPU fallback;
  2. kernels  — the paged decode kernel (bf16 and int8 arenas) and the
                dense decode kernel at smollm-135m decode shapes match
                their ``kernels/ref.py`` oracles;
  3. serving  — ``FaaSRuntime`` serves smollm-135m (30 layers, d_model
                576, vocab 49152, bf16) through the gateway: a static
                function with a baked system prompt and a LoRA function,
                8 requests that take the cold, fork and warm paths and
                reuse the baked prefix;
  4. parity   — the served (Pallas) prefill and 4 teacher-forced decode
                steps agree with the same model on XLA attention;
  5. fallback — the engine's compiled decode step holds the kernel as a
                ``tpu_custom_call``.

``--tp4`` runs only the four-chip phase: llama2-13b at full size served
tensor-parallel through ``FaaSRuntime(mesh=...)``, with a 4-layer copy's
logits compared between 4-way TP and one chip.  ``--cpu-rehearsal`` runs
the same phases at tiny sizes on the CPU (kernels in interpret mode) and
then exits non-zero: a rehearsal is not a chip run.

Weights are random, made from ``--seed``.  Timings printed here come from
one bring-up run and are not benchmark numbers.  The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache`` in the checkout; a second
run reports its hits.  The last line of a passing chip run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402  (backends start at first use, after main sets flags)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import api as tidal  # noqa: E402
from repro.core.template_server import TemplateServer  # noqa: E402
from repro.distributed.sharding import serving_plan  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.models.config import reduced  # noqa: E402
from repro.models.quant import quantize_rows  # noqa: E402
from repro.models.registry import get_config, get_model  # noqa: E402
from repro.runtime.continuous import (  # noqa: E402
    serve_fns, sharded_serve_fns)
from repro.runtime.faas import FaaSRuntime  # noqa: E402
from repro.runtime.gateway import InvocationRequest  # noqa: E402
from repro.runtime.kv_pool import PagedKVCachePool  # noqa: E402
from repro.utils import enable_compile_cache, fmt_bytes  # noqa: E402

# kernel outputs are bf16 (8 mantissa bits): compared with the f32 oracle
KERNEL_TOL = dict(atol=2e-2, rtol=2e-2)
# served Pallas path against XLA attention on the same chip, over the
# logits of the prefill and 4 teacher-forced decode steps: the largest
# |difference| may be at most this share of the largest |logit|.  Both
# paths keep bf16 activations through 30 layers and round attention
# differently (the kernel keeps f32 probabilities, XLA casts them to
# bf16); the same comparison in interpret mode on the CPU gave 1.9e-2
PALLAS_XLA_REL_TOL = 5e-2
# TP=4 against one chip on the 4-layer llama2-13b copy (same measure):
# sharded matmuls reduce their partial sums in another order
TP_REL_TOL = 5e-2
DECODE_STEPS = 4


class PhaseFailed(RuntimeError):
    """A phase's check did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_slots: int
    max_len: int
    page_size: int
    template: int          # shared system-prompt tokens of the static fn
    suffixes: tuple        # user-turn tokens after the template
    lora_lens: tuple       # prompt lengths of the LoRA function
    max_new: int


CHIP = Sizes(n_slots=8, max_len=1024, page_size=64, template=256,
             suffixes=(96, 160, 224, 320), lora_lens=(384, 512), max_new=32)
TINY = Sizes(n_slots=4, max_len=128, page_size=8, template=32,
             suffixes=(12, 20, 28, 36), lora_lens=(40, 56), max_new=4)
# --tp4 serves one static function: prompts of lora_lens[0] tokens
TP4 = Sizes(n_slots=4, max_len=256, page_size=64, template=0, suffixes=(),
            lora_lens=(128,), max_new=16)


def _parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp4", action="store_true",
                    help="run only the four-chip tensor-parallel phase")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU; always exits non-zero")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


class CacheCounter:
    """Counts persistent compile-cache hits and misses from JAX's events."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def phase(name: str):
    print(f"--- {name}", flush=True)
    return time.perf_counter()


def _model_cfg(arch: str, rehearsal: bool):
    cfg = get_config(arch)
    if rehearsal:
        # reduced widths, and the kernels pinned (interpret mode on the CPU)
        cfg = reduced(cfg, n_layers=2, attn_impl="pallas")
    return cfg


def _describe(cfg) -> str:
    return (f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
            f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV heads x "
            f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
            f"{cfg.dtype}")


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def _has_kernel(lowered_or_compiled) -> bool:
    return "tpu_custom_call" in lowered_or_compiled.as_text()


# ---------------------------------------------------------------------------
# phase 2: kernels against their oracles
# ---------------------------------------------------------------------------

def run_kernels(sizes: Sizes, cfg, rehearsal: bool, rng, seed: int) -> None:
    B, H, KV, d = sizes.n_slots, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ps, T = sizes.page_size, sizes.max_len
    NB = T // ps
    n_pages = 1 + B * NB
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    bf16 = jnp.bfloat16
    q = jax.random.normal(keys[0], (B, H, d), bf16)
    k_pages = jax.random.normal(keys[1], (n_pages, ps, KV, d), bf16)
    v_pages = jax.random.normal(keys[2], (n_pages, ps, KV, d), bf16)
    page_table = jnp.asarray(
        (rng.permutation(n_pages - 1) + 1)[:B * NB].reshape(B, NB), jnp.int32)
    lengths = np.concatenate([[1, T], rng.integers(1, T + 1, B - 2)])
    lengths = jnp.asarray(lengths, jnp.int32)
    kq, ks = quantize_rows(k_pages)
    vq, vs = quantize_rows(v_pages)
    k_dense = jax.random.normal(keys[3], (B, KV, T, d), bf16)
    v_dense = jax.random.normal(keys[4], (B, KV, T, d), bf16)

    cases = {
        "paged bf16": (lambda *a: ops.paged_decode_attention(*a),
                       ref.paged_decode_attention_ref,
                       (q, k_pages, v_pages, page_table, lengths)),
        "paged int8": (lambda q_, k_, v_, pt, ln, ks_, vs_:
                       ops.paged_decode_attention(q_, k_, v_, pt, ln,
                                                  k_scales=ks_, v_scales=vs_),
                       lambda q_, k_, v_, pt, ln, ks_, vs_:
                       ref.paged_decode_attention_ref(
                           q_, k_, v_, pt, ln, k_scales=ks_, v_scales=vs_),
                       (q, kq, vq, page_table, lengths, ks, vs)),
        "dense": (ops.decode_attention, ref.decode_attention_ref,
                  (q, k_dense, v_dense, lengths)),
    }
    for name, (kernel, oracle, args) in cases.items():
        fn = jax.jit(kernel)
        if not rehearsal:
            check(_has_kernel(fn.lower(*args)),
                  f"{name}: no tpu_custom_call in the lowered kernel")
        got = np.asarray(fn(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(oracle)(*args), np.float32)
        err = float(np.max(np.abs(got - want)))
        ok = np.allclose(got, want, **KERNEL_TOL)
        print(f"kernel {name}: B={B} H={H} KV={KV} d={d} pages of {ps}, "
              f"max_len {T}: max|kernel-ref| {err:.3e} "
              f"(atol {KERNEL_TOL['atol']}, rtol {KERNEL_TOL['rtol']}) "
              f"{'ok' if ok else 'FAIL'}")
        check(ok, f"{name}: kernel differs from its oracle")


# ---------------------------------------------------------------------------
# phase 3: serving through FaaSRuntime
# ---------------------------------------------------------------------------

def _prompt(rng, vocab: int, n: int) -> np.ndarray:
    return rng.integers(0, vocab, n).astype(np.int32)


def run_serving(sizes: Sizes, model, rng, seed: int) -> tuple:
    vocab = model.cfg.vocab_size
    rt = FaaSRuntime(n_slots=sizes.n_slots, max_len=sizes.max_len,
                     page_size=sizes.page_size)
    t0 = time.perf_counter()
    params_chat = model.init_params(jax.random.PRNGKey(seed))
    params_tr = model.init_params(jax.random.PRNGKey(seed + 1))
    template = _prompt(rng, vocab, sizes.template)
    rt.deploy(tidal.static_function("chat", model, params_chat), {},
              prewarm_seq=sizes.lora_lens[0], template_prompt=template)
    rt.deploy(tidal.lora_function("translate", model, params_tr,
                                  ["blocks.attn.wq"], n_adapters=2),
              {"adapter": "adapter-0"}, prewarm_seq=sizes.lora_lens[0])
    print(f"deployed 2 functions in {time.perf_counter() - t0:.1f}s "
          f"({rt.exe_cache.stats.misses} pre-warmed executables, "
          f"{sizes.template}-token system prompt baked)")

    chat = [np.concatenate([template, _prompt(rng, vocab, n)])
            for n in sizes.suffixes]
    lora = [_prompt(rng, vocab, n) for n in sizes.lora_lens]
    lora_b = _prompt(rng, vocab, sizes.lora_lens[0])
    a0, a1 = {"adapter": "adapter-0"}, {"adapter": "adapter-1"}
    plan = [("chat", {}, chat[0]), ("chat", {}, chat[1]),
            ("translate", a0, lora[0]), ("translate", a0, lora[1]),
            ("translate", a1, lora_b), ("chat", {}, chat[1]),
            ("evict chat", None, None), ("chat", {}, chat[2]),
            ("translate", a1, lora[0])]
    results = []
    for fn_name, event, prompt in plan:
        if prompt is None:
            rt.evict(fn_name.split()[1])
            continue
        handle = rt.submit(InvocationRequest(
            fn_name, prompt, event=event, max_new_tokens=sizes.max_new))
        res = handle.result()
        results.append(res)
        check(res.status == "done", f"{fn_name}: status {res.status}")
        print(f"req{len(results) - 1} {fn_name:9s} "
              f"{event.get('adapter', ''):9s} {res.kind:4s} "
              f"prompt {len(prompt):4d} reused {res.reused_prefix_len:4d} "
              f"ttft {res.ttft_s * 1e3:9.1f} ms "
              f"tokens {[int(t) for t in res.tokens[:6]]}...", flush=True)
        toks = np.asarray(res.tokens)
        check(len(toks) == sizes.max_new,
              f"{fn_name}: {len(toks)} tokens, expected {sizes.max_new}")
        check(bool(np.all((toks >= 0) & (toks < vocab))),
              f"{fn_name}: token outside the vocabulary")
    kinds = {r.kind for r in results}
    hits = sum(r.reused_prefix_len > 0 for r in results)
    print(f"kinds {sorted(kinds)}, prefix-reuse hits {hits} "
          "(one bring-up run: these TTFTs are not benchmark numbers)")
    check({"cold", "fork", "warm"} <= kinds, f"missing kinds: {kinds}")
    check(hits >= 1, "no request reused the baked system prompt")
    check(np.array_equal(results[1].tokens, results[5].tokens),
          "the same chat prompt gave different greedy tokens when warm")
    return rt, params_chat, chat[1]


# ---------------------------------------------------------------------------
# phases 4 and 5: parity and the kernel inside the served decode step
# ---------------------------------------------------------------------------

def teacher_forced(model, params, prefill, decode, prompt, forced,
                   sizes: Sizes, plan=None) -> tuple:
    """Logits of ``prompt``'s prefill and of decode steps fed ``forced``,
    through ``prefill``/``decode`` over a fresh paged arena; also returns
    the decode step's lowering at the engine's argument shapes."""
    pool = PagedKVCachePool(model, sizes.n_slots, sizes.max_len,
                            page_size=sizes.page_size, plan=plan)
    cache = model.make_cache(1, pool.padded_len)
    if plan is not None:
        cache = jax.device_put(cache, plan.cache_shardings(model, cache))
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt[None])},
                            cache)
    out = [np.asarray(logits[0], np.float32)]
    slot = pool.alloc(len(prompt), len(forced))
    pool.write_prompt(slot, cache, len(prompt))
    toks = np.zeros((sizes.n_slots, 1), np.int32)
    pos = np.zeros((sizes.n_slots,), np.int32)
    lowered = None
    for i, tok in enumerate(forced):
        toks[slot, 0] = tok
        pos[slot] = len(prompt) + i
        pool.ensure_len(slot, int(pos[slot]) + 1)
        args = (params, pool.cache, jnp.asarray(toks), jnp.asarray(pos),
                pool.device_page_table())
        if lowered is None:
            lowered = decode.lower(*args)
        logits, pool.cache = decode(*args)
        out.append(np.asarray(logits[slot], np.float32))
    return out, lowered


def _plain_serve_fns(model, page_size: int):
    """One-device prefill and paged decode, jitted as the runtime does."""
    prefill, _, decode = serve_fns(model, True, page_size)
    return prefill, decode


def run_parity(rt, model, params, prompt, sizes: Sizes, rng):
    """Returns the lowering of the engine's decode step."""
    forced = rng.integers(0, model.cfg.vocab_size, DECODE_STEPS)
    prefill, _, decode = rt._serve_fns_for("chat")
    served, lowered = teacher_forced(model, params, prefill, decode, prompt,
                                     forced, sizes)
    xla_model = get_model(model.cfg.replace(attn_impl="xla"))
    xla, _ = teacher_forced(xla_model, params,
                            *_plain_serve_fns(xla_model, sizes.page_size),
                            prompt, forced, sizes)
    errs = [_rel_err(a, b) for a, b in zip(served, xla)]
    worst = max(errs)
    print(f"parity served-vs-XLA, prompt {len(prompt)}, prefill + "
          f"{DECODE_STEPS} teacher-forced steps: max|diff|/max|logit| per "
          f"call {[f'{e:.2e}' for e in errs]}, worst {worst:.3e} "
          f"(tolerance {PALLAS_XLA_REL_TOL})")
    check(all(np.all(np.isfinite(a)) for a in served), "non-finite logits")
    check(worst <= PALLAS_XLA_REL_TOL, "served logits differ from XLA")
    return lowered


def run_no_fallback(lowered, rehearsal: bool) -> None:
    if rehearsal:
        print("skipped: kernels run in interpret mode on the CPU")
        return
    check(_has_kernel(lowered.compile()),
          "the engine's compiled decode step holds no tpu_custom_call")
    print("engine decode step compiled with the Pallas kernel")


# ---------------------------------------------------------------------------
# --tp4: llama2-13b tensor-parallel over four chips
# ---------------------------------------------------------------------------

def _shard_map_holds_kernel(text: str) -> bool:
    """True when a tpu_custom_call sits inside a shard_map body."""
    start = text.find("sdy.manual_computation")
    return start >= 0 and "tpu_custom_call" in text[start:]


def run_tp4(rehearsal: bool, rng, seed: int) -> None:
    devs = jax.devices()
    check(len(devs) >= 4, f"--tp4 needs 4 devices, found {len(devs)}")
    auto = (jax.sharding.AxisType.Auto,) * 2
    mesh = jax.make_mesh((1, 4), ("data", "model"), axis_types=auto,
                         devices=devs[:4])
    plan = serving_plan(mesh)
    sizes = TINY if rehearsal else TP4
    cfg = _model_cfg("llama2-13b", rehearsal)
    key = jax.random.PRNGKey(seed)

    t = phase("tp4 parity: 4-layer copy, TP=4 against one chip")
    small = get_model(cfg.replace(n_layers=4))
    print(f"model {_describe(small.cfg)}")
    p_tp = plan.init_params(small, key)
    p_one = jax.device_put(p_tp, devs[0])
    prompt = _prompt(rng, cfg.vocab_size, sizes.lora_lens[0])
    forced = rng.integers(0, cfg.vocab_size, DECODE_STEPS)
    pool_shape = PagedKVCachePool(small, sizes.n_slots, sizes.max_len,
                                  page_size=sizes.page_size, plan=plan)
    prefill_tp, _, decode_tp = sharded_serve_fns(small, pool_shape, plan)
    del pool_shape
    tp, lowered = teacher_forced(small, p_tp, prefill_tp, decode_tp, prompt,
                                 forced, sizes, plan=plan)
    one, _ = teacher_forced(small, p_one,
                            *_plain_serve_fns(small, sizes.page_size),
                            prompt, forced, sizes)
    errs = [_rel_err(a, b) for a, b in zip(tp, one)]
    print(f"TP=4 vs one chip: max|diff|/max|logit| per call "
          f"{[f'{e:.2e}' for e in errs]}, worst {max(errs):.3e} "
          f"(tolerance {TP_REL_TOL})")
    check(all(np.all(np.isfinite(a)) for a in tp), "non-finite logits")
    check(max(errs) <= TP_REL_TOL, "TP=4 logits differ from one chip")
    if rehearsal:
        print("kernel-in-shard_map check skipped: interpret mode on the CPU")
    else:
        check(_shard_map_holds_kernel(lowered.as_text()),
              "the sharded decode step holds no tpu_custom_call inside "
              "its shard_map")
        print("sharded decode step: tpu_custom_call inside the shard_map")
    del p_tp, p_one
    gc.collect()
    print(f"({time.perf_counter() - t:.1f}s)")

    t = phase("tp4 serving: full model through FaaSRuntime(mesh)")
    model = get_model(cfg)
    print(f"model {_describe(cfg)}")
    # no template residency: a resident copy next to a forked engine's
    # weights would not fit a model that needs all four chips
    server = TemplateServer(device_budget_bytes=0, plan=plan)
    rt = FaaSRuntime(server=server, mesh=mesh, n_slots=sizes.n_slots,
                     max_len=sizes.max_len, page_size=sizes.page_size)
    params = plan.init_params(model, key)
    fn = tidal.static_function("llama", model, params)
    del params
    gc.collect()
    rt.deploy(fn, {}, prewarm_seq=sizes.lora_lens[0])
    print(f"deployed in {time.perf_counter() - t:.1f}s")
    for i in range(3):
        prompt = _prompt(rng, cfg.vocab_size, sizes.lora_lens[0])
        res = rt.submit(InvocationRequest("llama", prompt,
                                          max_new_tokens=sizes.max_new)
                        ).result()
        check(res.status == "done", f"llama: status {res.status}")
        toks = np.asarray(res.tokens)
        check(len(toks) == sizes.max_new and bool(
            np.all((toks >= 0) & (toks < cfg.vocab_size))),
            "llama: wrong token count or token outside the vocabulary")
        print(f"req{i} llama {res.kind:4s} prompt {len(prompt)} "
              f"ttft {res.ttft_s * 1e3:9.1f} ms "
              f"tokens {[int(x) for x in toks[:6]]}...", flush=True)
    stats = [d.memory_stats() for d in devs[:4]]
    if all(s is not None for s in stats):
        used = [s["bytes_in_use"] for s in stats]
        print("bytes_in_use per device: "
              + ", ".join(f"{d.id}: {fmt_bytes(u)}"
                          for d, u in zip(devs[:4], used)))
        check(min(used) >= 0.5 * max(used),
              "device memory is not spread over the four chips")
    else:
        print("bytes_in_use: not reported by this backend")
    print(f"({time.perf_counter() - t:.1f}s)")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    args = _parse(argv)
    rehearsal = args.cpu_rehearsal
    if rehearsal and args.tp4:
        # XLA reads this when the CPU backend starts, at first device use
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
    t = phase("1 device")
    dev = jax.devices()[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {jax.device_count()}, jax {jax.__version__}", flush=True)
    if rehearsal:
        check(dev.platform == "cpu", "--cpu-rehearsal runs on the CPU only")
    else:
        check(dev.platform == "tpu", f"platform is {dev.platform}, not tpu")
    cache_dir = enable_compile_cache()
    counter = CacheCounter()
    print(f"compile cache: {cache_dir or 'off (CPU backend)'}")
    rng = np.random.default_rng(args.seed)

    if args.tp4:
        run_tp4(rehearsal, rng, args.seed)
        count = 4
    else:
        sizes = TINY if rehearsal else CHIP
        model = get_model(_model_cfg("smollm-135m", rehearsal))
        print(f"model {_describe(model.cfg)}")
        t = phase("2 kernels at decode shapes")
        run_kernels(sizes, model.cfg, rehearsal, rng, args.seed)
        print(f"({time.perf_counter() - t:.1f}s)")
        t = phase("3 serving through FaaSRuntime")
        rt, params, prompt = run_serving(sizes, model, rng, args.seed)
        print(f"({time.perf_counter() - t:.1f}s)")
        t = phase("4 parity: served Pallas path against XLA attention")
        lowered = run_parity(rt, model, params, prompt, sizes, rng)
        print(f"({time.perf_counter() - t:.1f}s)")
        t = phase("5 no hidden fallback")
        run_no_fallback(lowered, rehearsal)
        print(f"({time.perf_counter() - t:.1f}s)")
        count = 1
    print(f"compile cache: {counter.hits} hits, {counter.misses} misses")
    if rehearsal:
        print("rehearsal passed; exiting non-zero: the platform is not tpu")
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except PhaseFailed as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
