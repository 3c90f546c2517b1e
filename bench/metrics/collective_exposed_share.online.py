"""Share of the traced window in which a device ran a collective op
(all-reduce, all-gather, ... : ``trace_reduce.exposed_collective_s``)
and no other op: the cross-chip traffic that compute does not hide,
averaged over the devices. An op that only holds others (the ``while``
of the layer loop) is left out, as ``trace_reduce.top_ops`` leaves it
out: its span covers every collective inside the loop, which would
otherwise all read as hidden."""
import trace_reduce


def _without_containers(trace):
    return {**trace, "devices": {
        d: {**v, "ops": [o for o in v["ops"] if trace_reduce.short_name(
            o[0]) not in trace_reduce._CONTAINERS]}
        for d, v in trace["devices"].items()}}


def read(rec, cell):
    if rec.trace is None or not rec.trace["devices"]:
        return None
    lo, hi = trace_reduce.window(rec.trace)
    return 100.0 * trace_reduce.exposed_collective_s(
        _without_containers(rec.trace), lo, hi) / ((hi - lo) / 1e9)
