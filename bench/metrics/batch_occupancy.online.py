"""Mean share of the batcher's slots in use per round: the program's
``round`` trace events in the window, ``n_active`` over the slots."""


def read(rec, cell):
    ev = rec.program_events
    if not ev:
        return None
    off = rec.facts["server_offset"]
    lo, hi = rec.facts["w0"] - off, rec.facts["w1"] - off
    rounds = [e["n_active"] for e in ev
              if e["event"] == "round" and lo <= e["t"] < hi]
    if not rounds:
        return None
    return 100.0 * sum(rounds) / len(rounds) / rec.facts["n_slots"]
