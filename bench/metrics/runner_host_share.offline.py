"""Share of the measured window spent outside ``Engine.classify``: the
offline runners' own host work (orchestration, cold loads, store
pickling, merges), from the benchmark's spans around each call."""
import trace_reduce


def read(rec, cell):
    lo, hi = rec.facts["t0"], rec.facts["t1"]
    inside = trace_reduce.length(trace_reduce.clip(trace_reduce.union(
        (s, e) for n, s, e in rec.spans if n == "classify"), lo, hi))
    if hi <= lo:
        return None
    return 100.0 * (1.0 - inside / (hi - lo))
