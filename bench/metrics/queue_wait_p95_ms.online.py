"""95th percentile of the wait between a request's ``queued`` and
``admitted`` events in the program's own request trace
(``repro.obs.TraceRecorder``), over requests queued in the window."""
import gen


def read(rec, cell):
    ev = rec.program_events
    if not ev:
        return None
    off = rec.facts["server_offset"]
    lo, hi = rec.facts["w0"] - off, rec.facts["w1"] - off
    queued = {e["rid"]: e["t"] for e in ev
              if e["event"] == "queued" and lo <= e["t"] < hi}
    waits = [(e["t"] - queued[e["rid"]]) * 1e3 for e in ev
             if e["event"] == "admitted" and e.get("rid") in queued]
    return gen.percentile(waits, 95) if waits else None
