"""The classify step's share of the chip's bf16 peak: operations the
window's classify calls need (``bench/work.py``, from shapes) over the
device time of the classify executable in the trace, times the peak."""
import trace_reduce
import work

EXECUTABLE = r"classify"


def read(rec, cell):
    if rec.trace is None:
        return None
    lo, hi = trace_reduce.window(rec.trace)
    dev_s, runs = trace_reduce.module_seconds(rec.trace, lo, hi, EXECUTABLE)
    if runs == 0 or dev_s <= 0:
        return None
    f = rec.facts
    flops = runs * f["chunk_items"] * work.classify_flops(cell.config,
                                                          f["seq_len"])
    peak = work.peaks(rec.facts["device_kind"])["bf16_flops"]
    return 100.0 * flops / (dev_s * peak)
