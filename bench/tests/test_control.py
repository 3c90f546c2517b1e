"""The control, at a size a test run holds: the plain reference in
float8 e4m3 (weights and activations of every weight product), put in
the program's place, reads worse than the program on the same sample. On the chip at the cells' own sizes the control's
readings set the upper end of each limit (``bench/calibrate.py``;
PERF.md section 4)."""
import json
import time

import calibrate
import harness
from test_faults import DEC, ENC, OFFLINE, ONLINE, SEED

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def _record(workload, config, traffic, seconds):
    import jax
    return harness.run_record(BENCH, workload, seed=SEED, seconds=seconds,
                              trace=False, t_start=time.perf_counter(),
                              devices=jax.devices(), config_override=config,
                              traffic_override=traffic)


def test_offline_control_reads_worse_than_the_program():
    cell, rec = _record("distilbert-imdb.offline-512", ENC, OFFLINE, 1.5)
    prog = {c.name: c.value for c in rec.checks}
    ctl = calibrate.control_readings(cell, rec, SEED)
    print("program", prog, "control", ctl)
    assert ctl["logit_err"] > 3 * prog["logit_err"]
    assert ctl["logit_err_mean"] > 3 * rec.facts["logit_err_mean"]


def test_online_control_reads_worse_than_the_program():
    """On the run's own sample, position by position, the token that the
    fp8 control ranks first lies further below the reference's best than
    any token the program served."""
    cell, rec = _record("qwen2-7b-8L.chat-steady", DEC, ONLINE, 3.0)
    prog = {c.name: c.value for c in rec.checks}
    ctl = calibrate.control_readings(cell, rec, SEED)
    print("program", prog, "control", ctl)
    assert rec.facts["check_seqs"]
    assert ctl["token_gap"] > 3 * prog["token_gap"]
