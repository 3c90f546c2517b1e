"""Runs one cell of ``BENCHMARK.json`` and builds its result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the configuration as run, its source,
  the ``kind`` of model (``bench/models/<kind>.py``: parameter layout and
  plain reference) and the ``program`` mapping onto the program's config;
* ``bench/traffic/<traffic>.json``: the mix, and the ``driver``
  (``bench/drivers/<driver>.py``) that offers it to the program;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric.

A driver's ``run(ctx)`` returns a ``Record``: the end-to-end numbers, the
spans and counters the readers read, the device trace, and the numbers
compared for ``correct`` with their limits.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import pathlib
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def configure_jax(root: pathlib.Path = ROOT):
    """The persistent compile cache in the checkout, at a fixed path,
    whatever the environment says (two checkouts never share compiled
    programs), caching every program however fast it compiled. Call
    before the first compile."""
    cache = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def load_json(path: pathlib.Path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_module(path: pathlib.Path):
    """Import a benchmark file by path (metric names hold dots)."""
    name = "bench_" + str(path.resolve()).replace("/", "_").replace(
        ".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # the metrics this cell reports, trace off
    per_layer: List[dict]       # ... and with the trace on
    bench_dir: pathlib.Path     # where its models, drivers, metrics are

    def module(self, kind: str, name: str):
        """``bench/<kind>/<name>.py``, e.g. ``("metrics", "step_mfu.online")``."""
        return load_module(self.bench_dir / kind / f"{name}.py")


def resolve(bench: dict, workload: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell named ``workload`` with its files read."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} (have {sorted(cells)})")
    w = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = load_json(root / confs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    return Cell(workload, w["chips"], conf, traffic,
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)],
                root / "bench")


def program_config(conf: dict):
    """The program's ``ModelConfig``: its registry entry for
    ``program.arch`` with every field in ``program.fields`` set from the
    configuration's key of that name, so the program runs what the file
    states. Heads split the model width evenly (``head_dim``)."""
    from repro import configs
    base = configs.get(conf["program"]["arch"])
    fields = {f: conf[k] for f, k in conf["program"]["fields"].items()}
    cfg = dataclasses.replace(base, name=conf["name"], **fields)
    return dataclasses.replace(cfg, head_dim=cfg.d_model // cfg.n_heads)


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Record:
    """What a driver hands back."""
    end_to_end: Dict[str, float]
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: int
    spans: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)          # the benchmark's host spans
    program_events: List[dict] = dataclasses.field(default_factory=list)
    trace: Optional[dict] = None       # trace_reduce.load(...) of the window
    facts: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Context:
    """What a driver is given."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float                     # process start, perf_counter
    model: Any                         # bench/models/<kind>.py
    program_cfg: Any
    devices: list
    tmp: str                           # scratch for this run's files
    log: Callable[[str], None]

    def setup_s(self) -> float:
        return time.perf_counter() - self.t_start


class Profile:
    """The device trace of the measured window (a no-op with the trace
    off). Host spans go into the same trace as ``bench:<name>``."""

    def __init__(self, ctx: Context):
        self.on, self.dir = ctx.trace, os.path.join(ctx.tmp, "profile")
        self.trace: Optional[dict] = None

    def start(self):
        if self.on:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if self.on:
            import jax
            jax.profiler.stop_trace()
            import trace_reduce
            self.trace = trace_reduce.load(trace_reduce.find_xplane(
                self.dir))


def annotate(name: str):
    """A host span in the device trace (free when no trace is running)."""
    import jax
    return jax.profiler.TraceAnnotation("bench:" + name)


def memory_peak(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def run_cell(bench: dict, workload: str, **kw) -> Dict:
    """Run one cell and return its result object (the last stdout line)."""
    cell, rec = run_record(bench, workload, **kw)
    return result(cell, rec, kw["devices"])


def run_record(bench: dict, workload: str, *, seed: int, seconds: float,
               trace: bool, t_start: float, devices: list,
               root: pathlib.Path = ROOT,
               config_override: Optional[dict] = None,
               traffic_override: Optional[dict] = None,
               log: Callable[[str], None] = lambda s: None):
    """Run one cell; returns (cell, the traffic driver's Record)."""
    cell = resolve(bench, workload, root)
    if config_override:
        cell.config = {**cell.config, **config_override}
    if traffic_override:
        cell.traffic = {**cell.traffic, **traffic_override}
    model = cell.module("models", cell.config["kind"])
    driver = cell.module("drivers", cell.traffic["driver"])
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        ctx = Context(cell, seed, seconds, trace, t_start, model,
                      program_config(cell.config), devices, tmp, log)
        rec = driver.run(ctx)
        gc.collect()
    rec.facts.update(device_kind=devices[0].device_kind,
                     layout=model.layout(cell.config))
    return cell, rec


def _per_layer(cell: Cell, rec: Record) -> Dict[str, Any]:
    out = {}
    for m in cell.per_layer:
        v = cell.module("metrics", m["name"]).read(rec, cell)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def result(cell: Cell, rec: Record, devices: list) -> Dict:
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes":
              rec.memory_peak_bytes}
    out: Dict[str, Any] = {"correct": all(c.ok for c in rec.checks),
                           "attempted": rec.attempted, "failed": rec.failed}
    if rec.trace is not None:
        import trace_reduce
        s = trace_reduce.summary(rec.trace)
        device.update(busy_s=s["busy_s"], window_s=s["window_s"])
        out["metrics"] = _per_layer(cell, rec)
        out["device"] = device
        out["breakdown"] = s["breakdown"]
    else:
        out["metrics"] = {m["name"]: {"value": rec.end_to_end[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in rec.checks}
    return out
