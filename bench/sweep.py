"""Find the highest rate an open-loop cell sustains: one set-up, then one
window per offered rate, on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 0.2,0.4,0.8

For each rate it prints one JSON line: requests, finished, time to first
token, token gaps, output tokens per second against the offered rate, and
whether the tail grew from the window's first half to its second (a
growing queue).  The cell's fixed rate is set from this once, at about
four fifths of the highest sustained rate.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--drain", type=float, default=30.0)
    args = ap.parse_args(argv)

    import jax
    from benchlib import driver, readers, spec, stats, traffic
    from benchlib.harness import Ctx, log
    from repro.utils import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("no measurement: no TPU", file=sys.stderr)
        return 2
    w = spec.workload(args.workload)
    cell, cfg, mix = (spec.cell(w["name"]), spec.config(w["config"]),
                      spec.mix(w["traffic"]))
    enable_compile_cache()
    run = driver.Runtime(cfg, cell, mix, args.seed, log=log)
    vocab = cfg["vocab_size"]
    rates = [float(r) for r in args.rates.split(",")]
    scheds = [traffic.open_loop(mix, cell["functions"], vocab, rate,
                                args.seconds, args.seed + i)
              for i, rate in enumerate(rates)]
    run.warm_up(traffic.unshared_lengths(mix, sum(scheds, [])), log=log)
    for rate, sched in zip(rates, scheds):
        recs, _ = driver.open_loop(run, sched, args.seconds, None,
                                   drain_s=args.drain)
        ctx = Ctx(recs, args.seconds, 0.0, cfg, cell, {}, {})
        half = args.seconds / 2
        early = [r for r in recs if r["due"] < half]
        late = [r for r in recs if r["due"] >= half]
        out = {
            "rate": rate, "requests": len(recs),
            "finished": len(stats.done(recs)),
            "ttft_p50_ms": readers.ttft_ms(ctx, 50),
            "tpot_p50_ms": readers.tpot_ms(ctx, 50),
            "gateway_lag_p95_ms": stats.percentile(
                [(r["submit_at"] - r["due"]) * 1e3 for r in recs], 95),
            "ttft_p95_ms": readers.ttft_ms(ctx, 95),
            "tpot_p95_ms": readers.tpot_ms(ctx, 95),
            "ttft_p95_ms_first_half": readers.ttft_ms(
                Ctx(early, 0, 0, cfg, cell, {}, {}), 95),
            "ttft_p95_ms_second_half": readers.ttft_ms(
                Ctx(late, 0, 0, cfg, cell, {}, {}), 95),
            "output_tokens_per_s": stats.tokens_in(recs, 0.0, args.seconds)
            / args.seconds,
            "offered_tokens_per_s": sum(r["max_new"] for r in recs)
            / args.seconds,
        }
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
