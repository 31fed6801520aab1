"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Everything the cell needs is found by
the cell's name: BENCHMARK.json names its configuration and traffic mix,
``bench/cells/<cell>.json`` holds its deployment, and each metric is read
by ``bench/metrics/<metric>.py``.  Without a TPU (or with fewer chips than
the cell asks for) it exits with code 2 and prints no result.

``--rehearse`` runs every step at tiny sizes on whatever device there is,
prints what it got to standard error and exits with code 3: a check of the
harness, never a measurement.  ``--control 1`` also reads the float8
control's gap at the same positions (for setting the cell's limit).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", default=None,
                    help="directory to keep the profiler trace in")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from benchlib import harness, spec

    import jax
    chips = spec.workload(args.workload)["chips"]
    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu"
                              or len(devices) < chips):
        print(f"no measurement: the cell needs {chips} TPU chip(s), JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), T_START, rehearse=args.rehearse,
                         control=bool(args.control),
                         keep_trace=args.keep_trace)
    for k, v in result["check"].items():
        print(f"check: {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(f"check: correct {result['correct']}", file=sys.stderr, flush=True)
    if args.rehearse:
        print("rehearsal (not a measurement): " + json.dumps(result),
              file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
