"""The benchmark: one cell of ``BENCHMARK.json``, one process.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by name, makes the weights and
inputs from ``--seed``, warms up every shape the traffic uses (set-up),
offers the traffic for ``--seconds``, checks what the timed path produced
against a plain float32 reference, and prints one JSON line: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a device trace of the window. The numbers
compared for ``correct`` end standard error, each beside its limit, and
end the result line under ``checks``.

It runs only on a TPU with as many chips as the cell asks for; anywhere
else it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def _log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench_file = ROOT / "BENCHMARK.json"
    if not bench_file.exists():
        _log(f"no {bench_file}")
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        _log("the program (src/repro) is not in this checkout")
        return 2
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import harness
    jax = harness.configure_jax()
    bench = json.loads(bench_file.read_text())
    cell = harness.resolve(bench, args.workload)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        _log(f"no accelerator: {e}")
        return 2
    if devices[0].platform != "tpu":
        _log(f"no TPU (jax found {devices[0].platform}); the benchmark "
             f"runs only on the chip")
        return 2
    if len(devices) < cell.chips:
        _log(f"{args.workload} needs {cell.chips} chips; jax sees "
             f"{len(devices)}")
        return 2

    out = harness.run_cell(bench, args.workload, seed=args.seed,
                           seconds=args.seconds, trace=bool(args.trace),
                           t_start=T_START, devices=devices[:cell.chips],
                           log=_log)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
