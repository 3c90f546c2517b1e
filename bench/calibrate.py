"""The readings that the limits on ``correct`` are set from.

    python bench/calibrate.py --workload <name> --seconds <s> \
        --seeds 1,2,3 [--control 1,2,3]

Runs the cell once per seed in one process, each run exactly as
``bench/run.py`` runs it, and prints one JSON line per run with the
numbers compared and, for the seeds in ``--control``, the control's
readings: the plain reference put in the program's place and computed
in float8 e4m3, weights and activations of every weight product (the
precision below the configuration's bfloat16), read on the same
requests or reviews. The benchmark's own runs never run
the control. Runs only on a TPU, like the benchmark.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def control_readings(cell, rec, seed: int) -> dict:
    """The control's numbers on the run's own sample."""
    import numpy as np
    model = cell.module("models", cell.config["kind"])
    f = rec.facts
    if "check_seqs" in f:
        gaps = model.served_gaps(cell.config, seed, f["check_seqs"],
                                 f["check_prompts"], control=True)
        return {"token_gap": float(max(float(np.max(g)) for g in gaps)),
                "token_gap_mean": float(np.mean(np.concatenate(gaps)))}
    ref = model.logits(cell.config, seed, f["check_tokens"])
    low = model.logits(cell.config, seed, f["check_tokens"], control=True)
    driver = cell.module("drivers", cell.traffic["driver"])
    return {"logit_err": driver.logit_err(low, ref),
            "logit_err_mean": driver.logit_err_mean(low, ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import harness
    jax = harness.configure_jax()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: runs only on a TPU", file=sys.stderr)
        return 2
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    control = {int(s) for s in args.control.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        cell, rec = harness.run_record(
            bench, args.workload, seed=seed, seconds=args.seconds,
            trace=False, t_start=t0, devices=devices[:1],
            log=lambda s: print("bench:", s, file=sys.stderr, flush=True))
        line = {"seed": seed, "correct": all(c.ok for c in rec.checks),
                "checks": {c.name: c.value for c in rec.checks},
                "gaps": {k: rec.facts[k] for k in (
                    "logit_err", "logit_err_mean") if k in rec.facts},
                "end_to_end": rec.end_to_end}
        if seed in control:
            line["control"] = control_readings(cell, rec, seed)
        line["wall_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
