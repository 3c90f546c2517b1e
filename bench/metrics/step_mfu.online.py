"""The decode step's least time on the chip over its device time: the
least time is the larger of its operations over the bf16 peak and its
bytes over HBM bandwidth (``bench/work.py``): every weight once a step,
the live cache positions only. Tokens and their context lengths come
from the streams the clients received in the traced part of the
window; steps and device time from the decode executable's runs in the
trace."""
import trace_reduce
import work

EXECUTABLE = r"_ds|decode"


def read(rec, cell):
    if rec.trace is None:
        return None
    lo, hi = trace_reduce.window(rec.trace)
    dev_s, runs = trace_reduce.module_seconds(rec.trace, lo, hi, EXECUTABLE)
    a, b = rec.facts["traced"]
    toks = [(p, j) for x, p, j in rec.facts["tokens"]
            if j >= 1 and a <= x < b]
    if runs == 0 or dev_s <= 0 or not toks:
        return None
    c = cell.config
    sum_ctx = float(sum(p + j for p, j in toks))
    flops = work.decode_flops(c, len(toks), sum_ctx)
    nbytes = work.decode_bytes(c, rec.facts["layout"], runs, len(toks),
                               sum_ctx)
    pk = work.peaks(rec.facts["device_kind"])
    least = max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / (dev_s / len(rec.trace["devices"]))
