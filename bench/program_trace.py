"""The program's own trace, as its per-layer metrics read it.

Two sources, both written by the program and both absent from a build
that predates them (each helper then returns ``None`` and the metric is
left out of the result line):

* the request trace (``repro.obs.TraceRecorder`` JSONL, which
  ``bench/drivers/online.py`` hands over as ``Record.program_events``):
  under the wall clock it opens with a ``clock`` event whose
  ``monotonic`` is the server clock's zero on the host's
  ``time.monotonic()`` scale, the scale the clients stamp and the
  record's ``w0``/``w1`` are on;
* the program's host spans in the device trace (``repro:<name>``,
  ``repro.obs.span``), on the profiler's clock.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import trace_reduce

PREFIX = "repro:"


def server_window(rec) -> Optional[Tuple[float, float]]:
    """The measured window ``[w0, w1)`` on the server's clock, placed by
    the trace's ``clock`` event (not by an estimate of the offset)."""
    origin = next((e["monotonic"] for e in rec.program_events
                   if e["event"] == "clock"), None)
    if origin is None:
        return None
    return rec.facts["w0"] - origin, rec.facts["w1"] - origin


def spans(trace, name: str, lo: float, hi: float) -> List[List[float]]:
    """Where the host was inside ``repro:<name>`` within [lo, hi): the
    union of those host events, clipped."""
    return trace_reduce.clip(trace_reduce.union(
        (s, e) for n, s, e in trace["host"] if n == PREFIX + name), lo, hi)


def idle_inside_share(trace, name: str) -> Optional[float]:
    """Percent of the measured window in which the host was inside
    ``repro:<name>`` and the device ran no op (averaged over the devices
    traced); ``None`` where the trace holds no such span."""
    if trace is None or not trace["devices"]:
        return None
    lo, hi = trace_reduce.window(trace)
    inside = spans(trace, name, lo, hi)
    if not inside:
        return None
    per = [trace_reduce.length(trace_reduce.subtract(inside, b))
           for b in trace_reduce.busy(trace, lo, hi).values()]
    return 100.0 * sum(per) / len(per) / (hi - lo)
