"""Share of the batcher's rounds spent in admissions' prefills: time
inside ``repro:prefill`` (dispatch to first token on the host) over time
inside ``repro:round`` (``ContinuousBatcher.step``), in the traced
window."""
import program_trace
import trace_reduce


def read(rec, cell):
    if rec.trace is None:
        return None
    lo, hi = trace_reduce.window(rec.trace)
    rounds = trace_reduce.length(program_trace.spans(rec.trace, "round",
                                                     lo, hi))
    if rounds <= 0:
        return None
    prefill = trace_reduce.length(program_trace.spans(rec.trace, "prefill",
                                                      lo, hi))
    return 100.0 * prefill / rounds
