"""95th percentile of the time to first token at the client, timed from
when each request was due, over every request due in the window (one
never served counts as infinitely late): the online driver's measure,
read here per layer because a stall of the host adds to it whole (a run
in which the machine stands still for a second reads two to four times
the others)."""


def read(rec, cell):
    return rec.end_to_end.get("ttft_p95_ms")
