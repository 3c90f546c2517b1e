"""The ``decode_attention_partials`` kernel's least time over its device
time, the chips together. The least time is the larger of its
operations over the chips' combined bf16 peak and its bytes over their
combined HBM bandwidth (``bench/mesh_work.py``): each live cache
position once, and on every chip each token's query and its partials.
Tokens and their context lengths come from the streams the clients
received in the traced part of the window; device time is the kernel's
op time in the window, averaged over the devices."""
import mesh_work
import trace_reduce


def kernel_seconds(trace, lo, hi) -> float:
    """The kernel's op seconds inside [lo, hi), summed over devices."""
    total = 0.0
    for v in trace["devices"].values():
        for name, s, e in v["ops"]:
            if mesh_work.KERNEL in name and \
                    trace_reduce.short_name(name) == mesh_work.KERNEL:
                total += trace_reduce.length(trace_reduce.clip([[s, e]],
                                                               lo, hi))
    return total / 1e9


def read(rec, cell):
    if rec.trace is None or not rec.trace["devices"]:
        return None
    lo, hi = trace_reduce.window(rec.trace)
    kern_s = kernel_seconds(rec.trace, lo, hi)
    n_tok, sum_ctx = mesh_work.traced_decode_tokens(rec)
    if kern_s <= 0 or not n_tok:
        return None
    n = len(rec.trace["devices"])
    c = cell.config
    least = mesh_work.least_s(mesh_work.partials_flops(c, sum_ctx),
                              mesh_work.partials_bytes(c, n, n_tok, sum_ctx),
                              rec.facts["device_kind"], n)
    return 100.0 * least / (kern_s / n)
