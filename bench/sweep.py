"""Find the knee of an online cell: the highest offered rate the server
sustains. Run once, when a cell is defined, on the chip:

    python bench/sweep.py --workload <name> --seconds <s> --rates 3,4,5

Runs the cell at each rate in one process and prints, per rate, the
end-to-end numbers, the mean number of streams open in the window, and
the time to first token of the window's first and last thirds of
requests: a queue that grows through the window shows as a last third
far above the first.

The last line names the knee: the highest rate at which this and every
lower rate sustained the load (no request failed, and the last third's
median TTFT stayed under 1.5 times the first third's plus 50 ms), and
four fifths of it, the rate a cell below the knee offers.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import harness
    jax = harness.configure_jax()
    import gen

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep: runs only on a TPU", file=sys.stderr)
        return 2
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    knee, held = None, True
    for rate in sorted(float(r) for r in args.rates.split(",")):
        t0 = time.perf_counter()
        _, rec = harness.run_record(
            bench, args.workload, seed=args.seed, seconds=args.seconds,
            trace=False, t_start=t0, devices=devices[:1],
            traffic_override={"rate_rps": rate},
            log=lambda s: print("bench:", s, file=sys.stderr, flush=True))
        by_due = sorted(rec.facts["ttft_by_due"])
        k = max(len(by_due) // 3, 1)
        first = [x for _, x in by_due[:k]]
        last = [x for _, x in by_due[-k:]]
        w0, w1 = rec.facts["w0"], rec.facts["w1"]
        open_s = sum(max(min(b, w1) - max(a, w0), 0.0)
                     for a, b in rec.facts["streams"])
        p50_first, p50_last = (gen.percentile(first, 50),
                               gen.percentile(last, 50))
        held = held and rec.failed == 0 and p50_last <= 1.5 * p50_first + 50
        knee = rate if held else knee
        print(json.dumps({
            "rate_rps": rate, "end_to_end": rec.end_to_end,
            "attempted": rec.attempted, "failed": rec.failed,
            "streams_open_mean": open_s / (w1 - w0),
            "ttft_p50_first_third_ms": p50_first,
            "ttft_p50_last_third_ms": p50_last,
            "correct": all(c.ok for c in rec.checks)}), flush=True)
    print(json.dumps({"knee_rps": knee, "four_fifths_rps":
                      None if knee is None else round(0.8 * knee, 2)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
