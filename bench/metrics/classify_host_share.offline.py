"""Share of the window inside ``Engine.classify`` (``repro:classify``:
tokens in to labels on the host) while the device ran no op: classify's
own host path (input transfer, dispatch, the logits' sync, the argmax
dispatch and its sync), apart from the device's work."""
import program_trace


def read(rec, cell):
    return program_trace.idle_inside_share(rec.trace, "classify")
