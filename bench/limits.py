"""Read the numbers a cell's ``correct`` limit is set from: for each seed,
one whole run of the cell (set-up, a window at the cell's own load, the
reference) with the float8 control read beside the served tokens, all in
one process so that set-up compiles once.

    python3 bench/limits.py --workload <cell> --seeds 11,12,13 --seconds 30

Prints one JSON line per seed: the served tokens' widest gap, the
control's, and how many requests and tokens were compared.  Not a
measurement of speed: ``bench/run.py`` is.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax
    from benchlib import harness

    if jax.devices()[0].platform != "tpu":
        print("no measurement: no TPU", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = harness.run(args.workload, seed, args.seconds, False, t,
                          control=True)
        print(json.dumps({
            "seed": seed, "attempted": out["attempted"],
            "failed": out["failed"],
            "served": out["check"]["served_max_logit_gap"]["value"],
            "control": out["check"]["control_max_logit_gap"]["value"],
            "limit": out["check"]["control_max_logit_gap"]["limit"],
            "seconds": time.perf_counter() - t}), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
