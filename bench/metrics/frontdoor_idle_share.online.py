"""Share of the traced window in which the device ran no op while the
host was inside ``repro:frontdoor``: the server loop's turn given to the
HTTP handlers between rounds (``EventRouter.serve``), the device idle
that delivery costs."""
import program_trace


def read(rec, cell):
    return program_trace.idle_inside_share(rec.trace, "frontdoor")
