"""95th percentile of the server's own time to first token: from a
request's ``queued`` event (the handler's submit) to its first ``sent``
event (the token handed to the client's socket), over requests queued
in the window. One never sent counts as infinitely late, as in
``ttft_p95_ms.online``; what lies between it and this (the client's
send to the handler's first turn, the socket to the client) is not the
program's."""
import gen
import program_trace


def read(rec, cell):
    win = program_trace.server_window(rec)
    if win is None:
        return None
    lo, hi = win
    queued, first = {}, {}
    for e in rec.program_events:
        if e["event"] == "queued" and lo <= e["t"] < hi:
            queued[e["rid"]] = e["t"]
        elif e["event"] == "sent":
            first.setdefault(e["rid"], e["t"])
    waits = [(first[r] - t) * 1e3 if r in first else float("inf")
             for r, t in queued.items()]
    return gen.percentile(waits, 95) if waits else None
