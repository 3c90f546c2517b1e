"""Benchmark entry point: discovers and runs every bench module.

Any module in benchmarks/ that exports ``bench() -> list`` of
``(name, us_per_call, derived)`` rows is picked up automatically —
fig2, roofline, serving_bench, orchestrator_bench, kernel_bench,
router_bench, and whatever lands next. Prints one
``name,us_per_call,derived`` CSV across all of them, so CI invokes ONE
command instead of tracking the module list:

    python benchmarks/run.py                       # everything
    python benchmarks/run.py --only serving,router # filter by name
    python benchmarks/run.py --record .            # + BENCH_*.json

``--record DIR`` writes each module's JSON record (modules declare the
filename via ``BENCH_RECORD`` and may shape the payload via
``record(rows) -> dict``; others get the standard rows payload). A
module that produces SEVERAL artifacts from one run exports
``record_files(rows) -> {filename: payload}`` instead — router_bench
uses this to emit both BENCH_4.json (modeled grid) and BENCH_5.json
(calibrated grid) from a single sweep.
"""
from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import pkgutil
import sys
import traceback

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT), str(ROOT / "src")):   # robust under `python benchmarks/run.py`
    if p not in sys.path:
        sys.path.insert(0, p)


def discover() -> list:
    """(short_name, module_name) for every bench module, sorted by name.

    Import happens lazily in ``main`` AFTER ``--only`` filtering, so a
    broken unrelated module neither kills a filtered run nor costs its
    import time — it surfaces as a per-module ERROR row instead."""
    names = []
    for info in sorted(pkgutil.iter_modules([str(HERE)]),
                       key=lambda m: m.name):
        if info.name == "run":
            continue
        short = info.name[:-len("_bench")] \
            if info.name.endswith("_bench") else info.name
        names.append((short, info.name))
    return names


def default_record(module_name: str, rows: list) -> dict:
    import jax
    return {"benchmark": module_name,
            "device_count": jax.device_count(),
            "backend": jax.default_backend(),
            "rows": [{"name": n, "us_per_call": round(us, 2), "derived": d}
                     for n, us, d in rows]}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module names (short or full, "
                         "e.g. 'serving,router_bench')")
    ap.add_argument("--record", default=None, metavar="DIR",
                    help="write each module's BENCH_RECORD json here")
    args = ap.parse_args(argv)
    from repro.launch import compile_cache
    compile_cache.enable()

    mods = discover()
    if args.only:
        keep = {n.strip() for n in args.only.split(",")}
        mods = [(short, full) for short, full in mods
                if short in keep or full in keep]
        missing = keep - {n for pair in mods for n in pair}
        if missing:
            raise SystemExit(f"unknown bench module(s): {sorted(missing)}; "
                             f"available: {[n for n, _ in discover()]}")

    failures = 0
    print("name,us_per_call,derived")
    for name, full in mods:
        try:
            mod = importlib.import_module(f"benchmarks.{full}")
            if not callable(getattr(mod, "bench", None)):
                continue
            rows = mod.bench()
            for row_name, us, derived in rows:
                print(f"{row_name},{us:.2f},{derived}")
            if args.record and hasattr(mod, "record_files"):
                for fname, payload in mod.record_files(rows).items():
                    with open(pathlib.Path(args.record) / fname, "w") as f:
                        json.dump(payload, f, indent=2)
                        f.write("\n")
            elif args.record and hasattr(mod, "BENCH_RECORD"):
                payload = (mod.record(rows) if hasattr(mod, "record")
                           else default_record(full, rows))
                path = pathlib.Path(args.record) / mod.BENCH_RECORD
                with open(path, "w") as f:
                    json.dump(payload, f, indent=2)
                    f.write("\n")
        except Exception as e:  # keep the harness going; report at the end
            failures += 1
            print(f"{name}/ERROR,0.00,{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark module(s) failed")


if __name__ == "__main__":
    main()
