"""Share of the measured window in which the device ran no operation
(profiler trace: one minus the union of op intervals over the window)."""
import trace_reduce


def read(rec, cell):
    if rec.trace is None or not rec.trace["devices"]:
        return None
    return trace_reduce.idle_share(rec.trace)
