"""From a ``jax.profiler`` trace to numbers.

``load`` reads the ``.xplane.pb`` the profiler writes and keeps three
kinds of events, each as ``(name, start_ns, end_ns)``:

* device ops: the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane;
* device modules: its ``XLA Modules`` line, one event per executable run;
* host events: every timed event on the ``/host:CPU`` plane, among them
  the benchmark's own ``bench:<span>`` annotations.

Device and host events share the profiler's clock. Everything else here
is a pure function of those lists, so ``bench/tests`` checks it on a
small recorded trace with numbers worked out by hand.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, end_ns

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all|send|recv",
                         re.IGNORECASE)
_SUFFIX = re.compile(r"(\.\d+)+$|\(\d+\)$")
# ops that only hold others: their time is that of the ops nested in them
_CONTAINERS = {"while", "conditional", "call"}
WINDOW = "bench:window"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "host": [...]}"""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    lines[key] = [(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                                  for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.duration_ns > 0)
    return {"devices": devices, "host": host}


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Sorted, disjoint cover of the given [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> List[List[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> List[List[float]]:
    """The parts of the disjoint sorted intervals ``a`` outside ``b``."""
    out, j = [], 0
    b = union(b)
    for s, e in union(a):
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def window(trace: Dict) -> Tuple[float, float]:
    """The measured window: the ``bench:window`` host annotation."""
    spans = [(s, e) for n, s, e in trace["host"] if n == WINDOW]
    if not spans:
        raise ValueError("the trace holds no bench:window annotation")
    return spans[-1]


def short_name(name: str) -> str:
    """``fusion.12`` -> ``fusion``; ``jit__ds(7)`` -> ``jit__ds``; an op
    named by its HLO text, ``%fusion.3 = bf16[8]{0} fusion(...)``, ->
    ``fusion``."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


def busy(trace: Dict, lo: float, hi: float) -> Dict[str, List[List[float]]]:
    """Per device: the union of its op intervals inside [lo, hi)."""
    return {d: clip(union((s, e) for _, s, e in v["ops"]), lo, hi)
            for d, v in trace["devices"].items()}


def idle_share(trace: Dict) -> float:
    """Percent of the measured window in which the device ran no op
    (averaged over the devices traced)."""
    lo, hi = window(trace)
    return 100.0 * (1.0 - busy_s(trace, lo, hi) / ((hi - lo) / 1e9))


def busy_s(trace: Dict, lo: float, hi: float) -> float:
    """Seconds in which an op ran, averaged over the devices traced."""
    per = busy(trace, lo, hi)
    if not per:
        return 0.0
    return sum(length(v) for v in per.values()) / len(per) / 1e9


def module_seconds(trace: Dict, lo: float, hi: float,
                   pattern: str) -> Tuple[float, int]:
    """Device seconds (summed over devices) and run count of the
    executables whose name matches ``pattern``, inside [lo, hi)."""
    rx = re.compile(pattern)
    total, runs = 0.0, 0
    for v in trace["devices"].values():
        for name, s, e in v["modules"]:
            if rx.search(name):
                c = clip([[s, e]], lo, hi)
                if c:
                    total += length(c)
                    runs += 1
    return total / 1e9, runs


def exposed_collective_s(trace: Dict, lo: float, hi: float) -> float:
    """Collective-op time during which no other op runs on that device,
    averaged over devices."""
    per = []
    for v in trace["devices"].values():
        kind = [(_COLLECTIVE.search(short_name(n)), s, e)
                for n, s, e in v["ops"]]
        coll = [(s, e) for c, s, e in kind if c]
        comp = [(s, e) for c, s, e in kind if not c]
        per.append(length(clip(subtract(coll, comp), lo, hi)))
    return sum(per) / len(per) / 1e9 if per else 0.0


def top_ops(trace: Dict, lo: float, hi: float, n: int = 10
            ) -> List[List]:
    """The ``n`` op names (numeric suffixes dropped) that took most device
    time, averaged over devices: [[name, seconds], ...]. A ``while`` or
    other op that only holds others is left out: its ops are counted."""
    tot: Dict[str, float] = {}
    ndev = max(len(trace["devices"]), 1)
    for v in trace["devices"].values():
        for name, s, e in v["ops"]:
            c = clip([[s, e]], lo, hi)
            if c:
                k = short_name(name)
                if k in _CONTAINERS:
                    continue
                tot[k] = tot.get(k, 0.0) + length(c) / ndev / 1e9
    return [[k, t] for k, t in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def host_activity(host: Sequence[Event], t: float) -> str:
    """What the host was doing at ``t``: the shortest ``bench:`` span that
    covers it, else the shortest other host event, else ``idle``."""
    cover = [(e - s, n) for n, s, e in host if s <= t < e and n != WINDOW]
    bench = [c for c in cover if c[1].startswith("bench:")]
    pick = min(bench or cover, default=None)
    return pick[1] if pick else "idle"


def idle_gaps(trace: Dict, lo: float, hi: float, n: int = 10
              ) -> List[List]:
    """The ``n`` longest stretches of the window in which the first
    device ran nothing, each named by the host's activity at its middle:
    [["<activity>", seconds], ...]."""
    if not trace["devices"]:
        return []
    dev = sorted(trace["devices"])[0]
    b = busy(trace, lo, hi)[dev]
    gaps = subtract([[lo, hi]], b)
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    return [[host_activity(trace["host"], (s + e) / 2), (e - s) / 1e9]
            for s, e in gaps[:n]]


def summary(trace: Dict) -> Dict:
    """Window, busy seconds and breakdown of one traced run."""
    lo, hi = window(trace)
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": busy_s(trace, lo, hi),
            "breakdown": {"device_ops": top_ops(trace, lo, hi),
                          "idle_gaps": idle_gaps(trace, lo, hi)}}
