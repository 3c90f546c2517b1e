"""The per-layer metrics that read the program's own trace, on small
records whose answers are worked out by hand.

Request trace (seconds): the server clock's zero is at monotonic 1000.0
(the ``clock`` event) and the window is [1010, 1020) on the clients'
scale, so [10, 20) on the server's. The record's ``server_offset``
(995.0) is deliberately wrong: read with it the window would be [15,
25), which holds other events. Device traces (nanoseconds): the window
is the ``bench:window`` span, [0, 1000).
"""
import pytest

import harness

D = "/device:TPU:0"


def _read(metric, rec):
    return harness.load_module(harness.HERE / "metrics" /
                               f"{metric}.py").read(rec, None)


def _record(events=(), trace=None, w0=1010.0, w1=1020.0):
    return harness.Record(end_to_end={}, checks=[], attempted=0, failed=0,
                          memory_peak_bytes=0, program_events=list(events),
                          trace=trace, facts={"w0": w0, "w1": w1,
                                              "server_offset": 995.0})


CLOCK = {"t": 0.0, "event": "clock", "monotonic": 1000.0}


def _sent(rid, t, lag_ms):
    return {"t": t, "event": "sent", "rid": rid,
            "committed": t - lag_ms / 1e3}


def test_stream_lag_reads_sent_events_in_the_window_on_the_clock_origin():
    # 20 tokens sent in [10, 20) with lags 1..20 ms: nearest-rank p95 is
    # the 19th, 19 ms. Tokens sent in [20, 25) lag 5 s: inside the window
    # the wrong offset gives, outside the true one.
    ev = [CLOCK] + [_sent(k % 3, 10.0 + 0.4 * k, k + 1) for k in range(20)]
    ev += [_sent(7, 20.0 + 0.5 * k, 5000.0) for k in range(10)]
    ev += [_sent(8, 9.9, 7000.0)]                      # before the window
    assert _read("stream_lag_p95_ms.online", _record(ev)) == \
        pytest.approx(19.0)


def test_first_token_sent_is_queued_to_first_sent_with_unsent_infinite():
    # requests 0..18 queued at 10 + 0.5 k, first sent (k + 1) * 10 ms
    # later (a second sent follows 1 s on, ignored); request 19 queued at
    # 19.9 is never sent. Sorted waits 10..190 ms, then inf: the 19th of
    # 20 is 190 ms. Requests queued at 9 and at 21 are outside.
    ev = [CLOCK]
    for k in range(19):
        tq = 10.0 + 0.5 * k
        ev.append({"t": tq, "event": "queued", "rid": k})
        ev.append(_sent(k, tq + (k + 1) / 100, 1.0))
        ev.append(_sent(k, tq + 1.0, 1.0))
    ev.append({"t": 19.9, "event": "queued", "rid": 19})
    ev.append({"t": 9.0, "event": "queued", "rid": 30})
    ev.append(_sent(30, 9.001, 0.5))
    ev.append({"t": 21.0, "event": "queued", "rid": 31})
    assert _read("first_token_sent_p95_ms.online", _record(ev)) == \
        pytest.approx(190.0)
    # a second request never sent: the 20th of 21 is inf
    ev.append({"t": 19.95, "event": "queued", "rid": 20})
    assert _read("first_token_sent_p95_ms.online", _record(ev)) == \
        float("inf")


@pytest.mark.parametrize("metric", ["stream_lag_p95_ms.online",
                                    "first_token_sent_p95_ms.online"])
def test_request_trace_metrics_need_the_clock_event(metric):
    ev = [{"t": 11.0, "event": "queued", "rid": 0}, _sent(0, 11.5, 3.0)]
    assert _read(metric, _record(ev)) is None        # a build without it
    assert _read(metric, _record()) is None          # the trace off


def _online_trace():
    # device busy [0, 300) and [500, 820); rounds [0, 400), [450, 800),
    # [850, 1100) (clipped to 1000), prefills inside them of 100, 60 and
    # 20 (clipped) ns; the front door's turns [400, 450) (idle whole) and
    # [800, 850) (idle from 820 on).
    return {"devices": {D: {"ops": [("fusion.1", 0, 300),
                                    ("fusion.2", 500, 820)],
                            "modules": []}},
            "host": [("bench:window", 0, 1000),
                     ("repro:round", 0, 400), ("repro:prefill", 10, 110),
                     ("repro:decode", 150, 390),
                     ("repro:frontdoor", 400, 450),
                     ("repro:round", 450, 800), ("repro:prefill", 460, 520),
                     ("repro:decode", 520, 790),
                     ("repro:frontdoor", 800, 850),
                     ("repro:round", 850, 1100),
                     ("repro:prefill", 980, 1050),
                     ("np.asarray(jax.Array)", 700, 790)]}


def test_prefill_share_is_prefill_time_over_round_time():
    # prefill 100 + 60 + 20 = 180 over rounds 400 + 350 + 150 = 900
    assert _read("prefill_share.online", _record(trace=_online_trace())) \
        == pytest.approx(20.0)


def test_frontdoor_idle_share_counts_only_idle_device_time():
    # idle inside the front door: 50 + 30 = 80 ns of 1000
    assert _read("frontdoor_idle_share.online",
                 _record(trace=_online_trace())) == pytest.approx(8.0)


def test_classify_host_share_averages_over_devices():
    # classify [50, 250) and [550, 950); device 0 busy [100, 200) and
    # [600, 900), so idle inside classify 50 + 50 + 50 + 50 = 200 ns;
    # device 1 busy throughout, 0 ns; averaged 100 ns of 1000
    trace = {"devices": {D: {"ops": [("fusion.1", 100, 200),
                                     ("fusion.2", 600, 900)],
                             "modules": []},
                         "/device:TPU:1": {"ops": [("fusion.1", 0, 1000)],
                                           "modules": []}},
             "host": [("bench:window", 0, 1000),
                      ("bench:classify", 40, 260),
                      ("repro:classify", 50, 250),
                      ("repro:classify", 550, 950)]}
    assert _read("classify_host_share.offline", _record(trace=trace)) == \
        pytest.approx(10.0)


@pytest.mark.parametrize("metric", ["prefill_share.online",
                                    "frontdoor_idle_share.online",
                                    "classify_host_share.offline"])
def test_trace_metrics_need_the_program_spans(metric):
    trace = {"devices": {D: {"ops": [("fusion.1", 0, 300)], "modules": []}},
             "host": [("bench:window", 0, 1000),
                      ("bench:classify", 0, 500)]}
    assert _read(metric, _record(trace=trace)) is None   # a build without
    assert _read(metric, _record()) is None              # the trace off
