"""Benchmark runner: one module per paper table/figure.

Prints ``name,value,derived`` CSV sections (see benchmarks/common.py).
"""

import importlib
import sys
import time
import traceback

MODULES = [
    "benchmarks.fig04_breakdown",
    "benchmarks.fig13_ttft",
    "benchmarks.fig14_template_size",
    "benchmarks.fig15_16_workload",
    "benchmarks.fig17_breakdown",
    "benchmarks.fig18_distributed",
    "benchmarks.fig19_traces",
    "benchmarks.fig20_order_overhead",
    "benchmarks.fig21_prefix_reuse",
    "benchmarks.fig_p95_ttft",
    "benchmarks.fig_predictive_prewarm",
    "benchmarks.fig_multitenant",
    "benchmarks.table3_merging",
    "benchmarks.roofline_table",
]


def main() -> None:
    failures = []
    for name in MODULES:
        print(f"\n==== {name} ====")
        t0 = time.perf_counter()
        try:
            mod = importlib.import_module(name)
            mod.main()
            print(f"# {name} done in {time.perf_counter() - t0:.1f}s")
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        print("\nFAILED:", failures)
        sys.exit(1)
    print("\nall benchmarks complete")


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()
