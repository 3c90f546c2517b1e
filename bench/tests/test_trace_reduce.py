"""The trace reduction on a small trace whose numbers are worked out by
hand (nanoseconds; the window is the bench:window span, 0-400).

Device 0 runs fusion.1 [0,100), all-reduce.2 [50,150), fusion.3
[200,260) and copy.4 [250,300): busy [0,150) and [200,300), 250 ns.
Device 1 runs fusion.1 [10,60): 50 ns. Busy averaged: 150 ns.
The all-reduce overlaps compute on [50,100) and is exposed on
[100,150): 50 ns on device 0, nothing on device 1, 25 ns averaged.
Device 0 idles on [150,200), while the host is in bench:invoke (the
classify span starts at 180, after the middle), and on [300,400),
while it runs PjitFunction(x).
"""
import json
import pathlib

import pytest

import trace_reduce as tr

TRACE = json.loads((pathlib.Path(__file__).parent / "small_trace.json")
                   .read_text())


def test_window():
    assert tr.window(TRACE) == (0, 400)


def test_busy_and_idle():
    assert tr.busy_s(TRACE, 0, 400) == pytest.approx(150e-9)
    s = tr.summary(TRACE)
    assert s["window_s"] == pytest.approx(400e-9)
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(0.625)
    assert tr.idle_share(TRACE) == pytest.approx(62.5)


def test_module_seconds():
    assert tr.module_seconds(TRACE, 0, 400, "classify") == (
        pytest.approx(210e-9), 2)
    assert tr.module_seconds(TRACE, 0, 400, "_ds") == (
        pytest.approx(100e-9), 1)
    # clipped to the window
    assert tr.module_seconds(TRACE, 250, 400, "_ds")[0] == pytest.approx(
        50e-9)


def test_exposed_collectives():
    assert tr.exposed_collective_s(TRACE, 0, 400) == pytest.approx(25e-9)


def test_breakdown():
    s = tr.summary(TRACE)["breakdown"]
    assert [n for n, _ in s["device_ops"]] == ["fusion", "all-reduce",
                                               "copy"]
    assert [t for _, t in s["device_ops"]] == pytest.approx(
        [105e-9, 50e-9, 25e-9])
    assert s["idle_gaps"] == [["PjitFunction(x)", pytest.approx(100e-9)],
                              ["bench:invoke", pytest.approx(50e-9)]]


def test_interval_helpers():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert tr.short_name("fusion.12") == "fusion"
    assert tr.short_name("jit__ds(7)") == "jit__ds"
    assert tr.short_name("%add_fusion.2 = bf16[8]{0} fusion(bf16[8]{0} "
                         "%all-reduce.1), kind=kLoop") == "add_fusion"
