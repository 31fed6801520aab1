"""Shared helpers for the paper-figure benchmarks.

Every benchmark prints CSV rows ``name,value,derived`` (value in ms unless
stated) and returns them for run.py to aggregate into bench_output.txt.
The latencies of the paper figures come from the calibrated analytical
cost model, not from a device.  The ``--measured`` modes run the reduced
configs live; run on the CPU (``JAX_PLATFORMS=cpu``) they check mechanisms,
not speed.  The serving path on a TPU is checked by ``chip_smoke.py``.
"""

from __future__ import annotations

import json
import os
import pathlib

from repro.core import costmodel as cm
from repro.hw import A6000_PCIE4

PAPER_HW = A6000_PCIE4
LORA_FRACTION = 0.01          # adapters < 1% of the base model (paper §2.3)


def lora_bytes(plan) -> int:
    return int(plan.total_weight_bytes * LORA_FRACTION)


def emit(rows, header=("name", "value_ms", "derived")):
    print(",".join(header))
    for r in rows:
        print(",".join(str(x) for x in r))
    return rows


def write_bench_json(fig: str, metrics: dict, gates: dict | None = None,
                     out_dir=None) -> pathlib.Path:
    """Persist a ``--measured`` run's machine-readable result.

    Writes ``BENCH_<fig>.json`` with the headline ``metrics``, the boolean
    ``gates`` the run asserted, and ``passed`` (the AND of all gates; True
    when the run reached this call with no gates, since assertions raise
    before we get here).  Destination is ``$BENCH_OUT_DIR`` when set, else
    ``benchmarks/out/`` next to this file.  Returns the written path.
    """
    if out_dir is None:
        out_dir = os.environ.get(
            "BENCH_OUT_DIR", pathlib.Path(__file__).parent / "out")
    out_dir = pathlib.Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"BENCH_{fig}.json"
    payload = {
        "fig": fig,
        "passed": all((gates or {}).values()),
        "gates": {k: bool(v) for k, v in (gates or {}).items()},
        "metrics": metrics,
    }
    path.write_text(json.dumps(payload, indent=2, default=float) + "\n")
    print(f"bench-json,{path},")
    return path


def strategies(plan, hw=PAPER_HW, dynamic: bool = False, template_bytes=0):
    dyn = lora_bytes(plan) if dynamic else 0
    return {
        "pytorch-pin": cm.ttft_load_then_infer(plan, hw).total,
        "serverlessllm": cm.ttft_load_then_infer(plan, hw,
                                                 host_factor=1.02).total,
        "tidal-0g": cm.ttft_tidal(plan, hw, template_bytes=0,
                                  dynamic_bytes=dyn).total,
        "tidal": cm.ttft_tidal(plan, hw, template_bytes=template_bytes,
                               dynamic_bytes=dyn).total,
        "tidal-warm": cm.ttft_tidal(plan, hw,
                                    template_bytes=plan.total_weight_bytes,
                                    dynamic_bytes=dyn).total,
        "execution": cm.ttft_execution(plan, hw).total,
    }
