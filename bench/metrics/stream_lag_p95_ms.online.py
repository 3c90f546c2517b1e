"""95th percentile of a token's lag between its commit in the batcher
and its hand-over to the client's socket: ``t - committed`` of the
program's ``sent`` events (the HTTP front door, once ``writer.drain()``
returns) written in the window. A backlog of undelivered tokens reads
here, not in the server's queue."""
import gen
import program_trace


def read(rec, cell):
    win = program_trace.server_window(rec)
    if win is None:
        return None
    lo, hi = win
    lags = [(e["t"] - e["committed"]) * 1e3 for e in rec.program_events
            if e["event"] == "sent" and lo <= e["t"] < hi]
    return gen.percentile(lags, 95) if lags else None
