"""Fig. 18: distributed (tensor-parallel) TTFT — llama2-13b / llama2-34b
(approximated by qwen2.5-32b, same class) / llama2-70b on 2/4/8 A100s.

Paper: Tidal-0G/4G/8G/Warm achieve 1.76~2.01x / 2.33~2.66x / 3.15~4.24x /
3.19~5.16x speedup over PyTorch-pin.

``--measured`` appends a LIVE tensor-parallel serve on forced host
devices (CPU): each attention family (dense GQA / moe / MLA) is deployed
on a multi-device mesh through the real FaaS runtime — weights stream
into NamedSharding buffers, the KV arena is sharded, GSPMD partitions
prefill/decode — reporting wall-clock warm/fork/cold service times and
verifying the sharded decode stream is token-identical to the
single-device ContinuousBatchingEngine.  A second section serves two
functions on a (data=2, model=tp/2) mesh to exercise the multi-instance
locality router.
"""

import os
import sys

if "--measured" in sys.argv:
    # must be set before the first jax backend touch: force enough host
    # devices for a live tensor-parallel serve (the analytic rows below
    # never initialize a backend)
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

from benchmarks.common import emit, write_bench_json
from repro.core import costmodel as cm
from repro.core.plans import plan_for
from repro.hw import A100_PCIE3

CASES = [("llama2-13b", 2), ("qwen2.5-32b", 4), ("llama2-70b", 8)]

# smoke-scale stand-ins for the live measured mode: one per attention
# family the sharded runtime serves (dense GQA / moe / MLA)
MEASURED_ARCHS = ["smollm-135m", "phi3.5-moe-42b-a6.6b", "deepseek-v3-671b"]


def measured_rows(tp: int = 4, max_new_tokens: int = 4):
    """Live tensor-parallel serve through the real runtime (CPU host
    devices), with token parity asserted against a single-device engine."""
    import jax
    import numpy as np

    from repro.core import api as tidal
    from repro.models.registry import get_smoke_model
    from repro.runtime.engine import Engine
    from repro.runtime.faas import FaaSRuntime, measure_service_times

    tp = min(tp, jax.device_count())
    if tp < 2:
        raise SystemExit("--measured needs >= 2 devices (set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    rows = []
    prompt_len, max_len = 8, 24
    for arch in MEASURED_ARCHS:
        mesh = jax.make_mesh((1, tp), ("data", "model"))
        m = get_smoke_model(arch, n_layers=2)
        params = m.init_params(jax.random.PRNGKey(0))
        rt = FaaSRuntime(n_slots=2, max_len=max_len, trace_seq=prompt_len,
                         mesh=mesh)
        rt.deploy(tidal.static_function(f"{arch}-tp{tp}", m, params), {},
                  prewarm_seq=prompt_len)
        mst = measure_service_times(rt, {f"{arch}-tp{tp}": {}},
                                    prompt_len=prompt_len,
                                    max_new_tokens=max_new_tokens)
        for kind in ("warm", "fork", "cold"):
            t = mst.service_s(f"{arch}-tp{tp}", kind)
            if t is not None:
                rows.append((f"{arch}-tp{tp}/measured-{kind}",
                             round(t * 1e3, 1), "wall-clock"))
        # parity: the sharded serve must reproduce the single-device
        # continuous-batching stream token for token
        rng = np.random.default_rng(1)
        prompt = rng.integers(0, m.cfg.vocab_size, prompt_len).astype(np.int32)
        want = Engine(m, params, donate_cache=False).generate(
            prompt[None], max_new_tokens=max_new_tokens,
            cache_len=max_len).tokens[0]
        got = rt.submit(f"{arch}-tp{tp}", {}, prompt, max_new_tokens).tokens
        parity = bool(np.array_equal(got, want))
        rows.append((f"{arch}-tp{tp}/token_parity_vs_1dev",
                     "ok" if parity else "MISMATCH", f"{tp}-way TP"))
        if not parity:
            raise SystemExit(f"{arch}: sharded decode diverged from the "
                             "single-device engine")

    # multi-instance placement: two functions on (data=2, model=tp//2),
    # the live analogue of the cluster scheduler's locality routing
    if jax.device_count() >= 4:
        mesh = jax.make_mesh((2, max(2, tp // 2)), ("data", "model"))
        m = get_smoke_model("smollm-135m", n_layers=2)
        params = m.init_params(jax.random.PRNGKey(0))
        rt = FaaSRuntime(n_slots=2, max_len=max_len, trace_seq=prompt_len,
                         mesh=mesh)
        for name in ("fn-a", "fn-b"):
            rt.deploy(tidal.static_function(name, m, params), {},
                      prewarm_seq=prompt_len)
        rng = np.random.default_rng(2)
        prompt = rng.integers(0, m.cfg.vocab_size, prompt_len).astype(np.int32)
        rt.submit("fn-a", {}, prompt, max_new_tokens)
        rt.submit("fn-b", {}, prompt, max_new_tokens)
        placed = {k[0]: w.instance for k, w in rt._engines.items()}
        rows.append(("multi-instance/placement",
                     "spread" if placed["fn-a"] != placed["fn-b"] else "co",
                     f"2 instances x {mesh.shape['model']}-way TP"))
    return rows


def main(measured: bool = False):
    rows = []
    for arch, tp in CASES:
        plan = plan_for(arch, 1, 4096)
        pin = cm.ttft_load_then_infer(plan, A100_PCIE3, tp=tp).total
        variants = {
            "tidal-0g": cm.ttft_tidal(plan, A100_PCIE3, tp=tp).total,
            "tidal-4g": cm.ttft_tidal(plan, A100_PCIE3, tp=tp,
                                      template_bytes=4 << 30).total,
            "tidal-8g": cm.ttft_tidal(plan, A100_PCIE3, tp=tp,
                                      template_bytes=8 << 30).total,
            "tidal-warm": cm.ttft_tidal(
                plan, A100_PCIE3, tp=tp,
                template_bytes=plan.total_weight_bytes).total,
        }
        rows.append((f"{arch}-tp{tp}/pytorch-pin", round(pin * 1e3, 1), ""))
        for k, v in variants.items():
            rows.append((f"{arch}-tp{tp}/{k}", round(v * 1e3, 1),
                         f"speedup={pin/v:.2f}x"))
    if measured:
        mrows = measured_rows()
        rows += mrows
        write_bench_json("fig18_distributed", {n: v for n, v, _ in mrows},
                         gates={"live_tp_serve_completed": bool(mrows)})
    return emit(rows)


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main(measured="--measured" in sys.argv)
