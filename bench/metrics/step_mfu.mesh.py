"""The decode step's least time on the mesh over its device time. The
least time is the larger of its operations over the chips' combined
bf16 peak and its bytes over their combined HBM bandwidth
(``bench/work.py``, ``bench/mesh_work.py``): every weight once a step
across the mesh, the live cache positions only. Tokens and their
context lengths come from the streams the clients received in the
traced part of the window; steps and device time from the decode
executable's runs in the trace, each step counted once and its time
averaged over the devices (``step_mfu.online`` reckons one chip)."""
import mesh_work
import trace_reduce
import work

EXECUTABLE = r"_ds|decode"


def read(rec, cell):
    if rec.trace is None or not rec.trace["devices"]:
        return None
    lo, hi = trace_reduce.window(rec.trace)
    dev_s, runs = trace_reduce.module_seconds(rec.trace, lo, hi, EXECUTABLE)
    n_tok, sum_ctx = mesh_work.traced_decode_tokens(rec)
    if runs == 0 or dev_s <= 0 or not n_tok:
        return None
    n = len(rec.trace["devices"])
    c = cell.config
    flops = work.decode_flops(c, n_tok, sum_ctx)
    nbytes = work.decode_bytes(c, rec.facts["layout"], runs / n, n_tok,
                               sum_ctx)
    least = mesh_work.least_s(flops, nbytes, rec.facts["device_kind"], n)
    return 100.0 * least / (dev_s / n)
