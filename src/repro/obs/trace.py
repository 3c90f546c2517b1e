"""Per-request trace spans as a structured JSONL event log, and the
program's host spans on the profiler's clock.

Every request's lifecycle is a span sequence

    queued -> admitted -> prefill -> first_token
           -> finish | cancel | expire          (or: queued -> reject)

plus one ``round`` event per replica round carrying the BENCH_8
time-attribution buckets (prefill / decode_attention / sampler /
host_scheduler) and the ``rids`` decoded in it. The recorder itself
never reads a clock — callers stamp every event with *their* clock's
time, so:

  * under ``VirtualClock`` the timestamps are the deterministic
    simulated times and two same-seed runs produce byte-identical
    trace files;
  * under ``WallClock`` the same call sites stamp seconds since the
    clock's zero, and the trace opens with one ``clock`` event whose
    ``monotonic`` field is ``time.monotonic()`` at that zero, so any
    process on the host can put its own ``time.monotonic()`` stamps on
    the trace's scale.

Delivery events (``sent``: one per token chunk the HTTP front door has
handed to its client's socket, with the ``committed`` time of the
token) are recorded apart from the lifecycle: the server can finish a
stream before its client has received it, so a ``sent`` may follow the
request's ``finish``. ``spans()`` holds the lifecycle only.

``span(name)`` is the other half: a ``jax.profiler.TraceAnnotation``
named ``repro:<name>``, which lands on the host line of a device trace
when a profiler runs and costs under a microsecond when none does.

Events are dicts ``{"t": float, "event": str, ...}`` appended to an
in-memory list (O(1) per event, no I/O on the hot path) and flushed to
JSONL by ``dump()``/``dumps()``. ``tools/trace_report.py`` turns the
file back into a per-request waterfall and a per-round bucket table;
``spans()`` groups events per request for the hypothesis monotonicity
laws in tests/test_property_invariants.py.
"""
from __future__ import annotations

import json
from typing import Dict, Iterable, List, Optional

import jax

# Request-lifecycle event names, in legal order of first occurrence.
SPAN_EVENTS = ("queued", "admitted", "prefill", "first_token", "finish",
               "cancel", "expire", "reject")
TERMINAL_EVENTS = ("finish", "cancel", "expire", "reject")
# Per-request delivery to the client; may follow the terminal event.
DELIVERY_EVENTS = ("sent",)
# Non-request events: the wall clock's origin, the engine's placement
# under a mesh, per-round attribution, pool/scaling transitions.
SYSTEM_EVENTS = ("clock", "layout", "round", "replica_start",
                 "replica_ready", "replica_crash", "replica_retire", "scale")
SPAN_PREFIX = "repro:"


def span(name: str) -> jax.profiler.TraceAnnotation:
    """The host span ``repro:<name>`` on the profiler's clock (a no-op
    costing under a microsecond when no profiler is running)."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


class TraceRecorder:
    """Append-only trace sink. Callers stamp times; we never clock."""

    def __init__(self) -> None:
        self.events: List[dict] = []

    def emit(self, event: str, t: float, rid: Optional[int] = None,
             **fields) -> None:
        rec: Dict = {"t": float(t), "event": event}
        if rid is not None:
            rec["rid"] = rid
        if fields:
            rec.update(fields)
        self.events.append(rec)

    def __len__(self) -> int:
        return len(self.events)

    # ---- serialization ------------------------------------------------

    def dumps(self) -> str:
        """One JSON object per line; key order fixed by insertion so
        same-seed virtual runs serialize byte-identically."""
        return "".join(json.dumps(e, separators=(",", ":")) + "\n"
                       for e in self.events)

    def dump(self, path: str) -> int:
        """Write JSONL to ``path``; returns the number of events."""
        with open(path, "w") as f:
            f.write(self.dumps())
        return len(self.events)

    # ---- span reads ---------------------------------------------------

    def spans(self) -> Dict[int, List[dict]]:
        """Lifecycle events grouped per rid, preserving emit order."""
        return spans_of(self.events)

    def terminal(self, rid: int) -> Optional[str]:
        """The request's terminal event name, or None if still open."""
        for e in reversed(self.events):
            if e.get("rid") == rid and e["event"] in TERMINAL_EVENTS:
                return e["event"]
        return None


def load_jsonl(path: str) -> List[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def spans_of(events: Iterable[dict]) -> Dict[int, List[dict]]:
    """`TraceRecorder.spans` over an already-loaded event list: each
    request's events, delivery events left out."""
    out: Dict[int, List[dict]] = {}
    for e in events:
        if "rid" in e and e["event"] not in DELIVERY_EVENTS:
            out.setdefault(e["rid"], []).append(e)
    return out
