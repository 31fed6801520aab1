"""Fig. 13: TTFT across LLM functions (input 2048, batch 1), with and
without LoRA, vs PyTorch-pin / ServerlessLLM / Execution.

Paper headline: Tidal-0G is 1.96x / 2.00x faster than PyTorch-pin /
ServerlessLLM on average; 22%~84% slower than Execution.

``--measured`` appends wall-clock warm/fork/cold TTFTs from the LIVE
serving runtime on a smoke-scale model (CPU), validating that the real
runtime reproduces the cost model's service-class ordering
(warm < fork < cold)."""

import sys

from benchmarks.common import PAPER_HW, emit, lora_bytes, write_bench_json
from repro.core import costmodel as cm
from repro.core.plans import plan_for

# the paper evaluates GPT-2-1.5B..Llama2-13B; our pool's closest spread
# (smollm-135m is far below the paper's range — it would inflate the
# average because the fixed 180 ms cold-kernel cost dominates tiny models)
ARCHS = ["gemma-2b", "llama3-8b", "llama2-13b", "qwen3-14b"]


def measured_rows():
    """Live smoke-model measurements through the real FaaS runtime."""
    from repro.runtime.faas import measure_smoke_service_times

    mst = measure_smoke_service_times({"smollm-live": "lora"})
    out = []
    for kind in ("warm", "fork", "cold"):
        t = mst.service_s("smollm-live", kind)
        if t is not None:
            out.append((f"smollm-live/measured-{kind}", round(t * 1e3, 1),
                        "wall-clock"))
    return out


def main(measured: bool = False):
    rows = []
    speedups_pin, speedups_sllm = [], []
    for arch in ARCHS:
        plan = plan_for(arch, 1, 2048)
        for lora in (False, True):
            dyn = lora_bytes(plan) if lora else 0
            tag = arch + ("-lora" if lora else "")
            pin = cm.ttft_load_then_infer(plan, PAPER_HW).total
            sllm = cm.ttft_load_then_infer(plan, PAPER_HW,
                                           host_factor=1.02).total
            t0g = cm.ttft_tidal(plan, PAPER_HW, template_bytes=0,
                                dynamic_bytes=dyn).total
            exe = cm.ttft_execution(plan, PAPER_HW).total
            rows += [
                (f"{tag}/pytorch-pin", round(pin * 1e3, 1), ""),
                (f"{tag}/serverlessllm", round(sllm * 1e3, 1), ""),
                (f"{tag}/tidal-0g", round(t0g * 1e3, 1),
                 f"speedup_vs_sllm={sllm/t0g:.2f}x"),
                (f"{tag}/execution", round(exe * 1e3, 1),
                 f"tidal_gap={(t0g-exe)/exe*100:.0f}%"),
            ]
            speedups_pin.append(pin / t0g)
            speedups_sllm.append(sllm / t0g)
    rows.append(("avg_speedup_vs_pin",
                 round(sum(speedups_pin) / len(speedups_pin), 2),
                 "paper=1.96x"))
    rows.append(("avg_speedup_vs_serverlessllm",
                 round(sum(speedups_sllm) / len(speedups_sllm), 2),
                 "paper=2.00x"))
    if measured:
        mrows = measured_rows()
        rows += mrows
        vals = {n.rsplit("-", 1)[-1]: v for n, v, _ in mrows}
        write_bench_json(
            "fig13_ttft", {n: v for n, v, _ in mrows},
            gates={"warm_below_fork_below_cold":
                   set(vals) >= {"warm", "fork", "cold"}
                   and vals["warm"] < vals["fork"] < vals["cold"]})
    return emit(rows)


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main(measured="--measured" in sys.argv)
