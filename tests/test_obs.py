"""Live observability layer: registry, traces, and the inertness proof.

The tentpole claims, pinned:

  * REGISTRY — Counter/Gauge/Histogram with fixed log-spaced buckets,
    labels, create-or-get semantics, and loud type/label conflicts;
    ``render()`` emits Prometheus text-exposition v0.0.4 that the
    independent re-parser ``lint_prometheus`` accepts, and the lint
    really rejects malformed expositions (it is a parser, not a rubber
    stamp).
  * TRACES — ``TraceRecorder`` never reads a clock; same-seed virtual
    runs serialize BYTE-IDENTICAL JSONL, and ``tools/trace_report.py``
    turns a real trace back into a waterfall + BENCH_8 bucket table.
  * INERT — obs on vs. off produces bit-identical token streams,
    timestamps, and report summaries across BOTH drivers (sync rounds,
    event loop) and BOTH cache layouts (dense, paged); with obs on,
    the counters agree exactly with the report.
  * FAULT ISOLATION — a raising ``on_token`` subscriber never corrupts
    batcher state, kills the round, or double-frees a row (dense +
    paged); faults are counted in ``on_token_errors``.
  * READINESS — ``/healthz`` readiness is False through the cold-start
    window AND until the engine has compiled an executable bucket
    (``Engine.warm``); ``live_stats`` serves the legacy JSON scrape
    without ever calling ``_report()`` (the old hot-path bug).
"""
import importlib.util
import pathlib

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import FaultInjector, LatencyModel
from repro.models import RunConfig, build
from repro.obs import (DEFAULT_BUCKETS, MetricsRegistry, Observability,
                       TERMINAL_EVENTS, TraceRecorder, lint_prometheus,
                       load_jsonl, log_buckets, spans_of)
from repro.router import (EventRouter, FixedReplicas, QueueConfig,
                          QueueDepthPolicy, ReplicaConfig, ReplicaPool,
                          Router, make_requests, poisson_arrivals)
from repro.serving import ContinuousBatcher, Engine, Request

PROMPT, NEW, SLOTS, MAXLEN = 8, 4, 2, 16
LAT = LatencyModel(cold_start_s=0.3, per_item_s=0.05)

_TOOLS = pathlib.Path(__file__).resolve().parents[1] / "tools"


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(name,
                                                  _TOOLS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def stack():
    cfg = configs.smoke("qwen2-7b")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, RunConfig(cache_pad=8))
    return model, cfg, engine, params


def _pool(engine, params, *, paged=False, lat=LAT):
    return ReplicaPool(engine, params,
                       ReplicaConfig(n_slots=SLOTS, max_len=MAXLEN,
                                     paged=paged, page_size=8),
                       lat=lat, injector=FaultInjector())


def _reqs(arrivals, cfg, **kw):
    return make_requests(arrivals, prompt_len=PROMPT, max_new_tokens=NEW,
                         vocab=cfg.vocab_size, seed=0, **kw)


# ---------------------------------------------------------------------------
# Registry: instruments + exposition
# ---------------------------------------------------------------------------


def test_log_buckets_fixed_sorted_and_covering():
    b = log_buckets(1e-2, 10.0, per_decade=2)
    assert list(b) == sorted(set(b))            # strictly increasing
    assert b[0] <= 1e-2 + 1e-12 and b[-1] >= 10.0
    assert DEFAULT_BUCKETS[0] <= 1e-4 and DEFAULT_BUCKETS[-1] >= 100.0
    with pytest.raises(ValueError):
        log_buckets(0.0, 1.0)
    with pytest.raises(ValueError):
        log_buckets(1.0, 0.5)


def test_counter_gauge_semantics_and_label_checks():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help", labelnames=("k",))
    c.inc(k="a")
    c.inc(2.5, k="a")
    c.inc(k="b")
    assert c.value(k="a") == 3.5 and c.value(k="b") == 1.0
    assert c.value(k="never") == 0.0
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1, k="a")
    with pytest.raises(ValueError, match="labels"):
        c.inc(wrong="a")
    g = reg.gauge("g", "help")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value() == 3.0
    # create-or-get returns the SAME instrument; conflicts are loud
    assert reg.counter("c_total", "help", labelnames=("k",)) is c
    with pytest.raises(ValueError, match="re-registered"):
        reg.gauge("c_total", "help")
    with pytest.raises(ValueError, match="re-registered"):
        reg.counter("c_total", "help")           # label-set mismatch


def test_histogram_observe_cumulative_quantile():
    reg = MetricsRegistry()
    h = reg.histogram("h_seconds", "help", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 500.0):
        h.observe(v)
    assert h.count() == 5 and h.sum() == pytest.approx(506.05)
    cum = h.cumulative()
    assert cum == [(0.1, 1), (1.0, 3), (10.0, 4), (float("inf"), 5)]
    assert h.quantile(0.5) == 1.0               # bucket-boundary estimate
    assert h.quantile(1.0) == 10.0              # +Inf folds to last bound
    assert np.isnan(reg.histogram("h2_seconds", "x").quantile(0.5))
    with pytest.raises(ValueError, match="increasing"):
        reg.histogram("h3", "x", buckets=(1.0, 1.0, 2.0))


def test_render_passes_the_independent_lint():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", labelnames=("path", "code"))
    c.inc(3, path='/v1/"gen"\n', code=200)      # escaping stress
    c.inc(path="/metrics", code=404)
    reg.gauge("depth", "queue depth").set(7)
    h = reg.histogram("lat_seconds", "latency", labelnames=("op",))
    for v in (0.001, 0.02, 0.3, 4.0):
        h.observe(v, op="decode")
    h.observe(0.5, op="prefill")
    text = reg.render()
    assert lint_prometheus(text) == []
    assert '# TYPE req_total counter' in text
    assert 'le="+Inf"' in text and "lat_seconds_count" in text


def test_promlint_rejects_malformed_expositions():
    # a sample with no TYPE preamble
    assert lint_prometheus("foo 1\n")
    # negative counter
    bad = ("# HELP c_total x\n# TYPE c_total counter\nc_total -1\n")
    assert any("negative" in e for e in lint_prometheus(bad))
    # histogram: non-monotone cumulative buckets
    bad = ("# HELP h x\n# TYPE h histogram\n"
           'h_bucket{le="0.1"} 5\nh_bucket{le="1"} 3\n'
           'h_bucket{le="+Inf"} 5\nh_sum 1\nh_count 5\n')
    assert any("monoton" in e for e in lint_prometheus(bad))
    # histogram: missing +Inf bucket
    bad = ("# HELP h x\n# TYPE h histogram\n"
           'h_bucket{le="0.1"} 5\nh_sum 1\nh_count 5\n')
    assert lint_prometheus(bad)
    # histogram: _count disagrees with the +Inf bucket
    bad = ("# HELP h x\n# TYPE h histogram\n"
           'h_bucket{le="+Inf"} 5\nh_sum 1\nh_count 4\n')
    assert lint_prometheus(bad)
    # malformed label syntax
    assert lint_prometheus("# HELP a x\n# TYPE a gauge\na{=} 1\n")


def test_observability_catalog_renders_clean_when_empty():
    """The full pre-created catalog (docs/OBSERVABILITY.md mirror) is
    valid exposition even before a single event lands."""
    obs = Observability()
    text = obs.registry.render()
    assert lint_prometheus(text) == []
    for name in ("repro_requests_total", "repro_ttft_seconds",
                 "repro_round_bucket_seconds_total", "repro_replicas",
                 "repro_http_inflight", "repro_page_pool_pages"):
        assert f"# TYPE {name} " in text


# ---------------------------------------------------------------------------
# Traces: determinism + round-trip + the report tool
# ---------------------------------------------------------------------------


def test_trace_recorder_deterministic_bytes_and_roundtrip(tmp_path):
    def drive(rec):
        rec.emit("queued", 0.0, rid=0)
        rec.emit("admitted", 0.3, rid=0, replica=0)
        rec.emit("round", 0.3, replica=0, round_s=0.2, n_active=1,
                 crashed=False, rids=[0])
        rec.emit("first_token", 0.35, rid=0)
        rec.emit("finish", 0.5, rid=0, n_tokens=4)

    a, b = TraceRecorder(), TraceRecorder()
    drive(a)
    drive(b)
    assert a.dumps() == b.dumps()               # byte-identical
    assert a.terminal(0) == "finish" and a.terminal(1) is None
    path = tmp_path / "trace.jsonl"
    assert a.dump(str(path)) == 5
    events = load_jsonl(str(path))
    assert events == a.events
    assert spans_of(events) == a.spans()
    assert [e["event"] for e in a.spans()[0]] == [
        "queued", "admitted", "first_token", "finish"]


def test_trace_report_tool_renders_waterfall_and_buckets(tmp_path):
    rec = TraceRecorder()
    rec.emit("queued", 0.0, rid=0)
    rec.emit("admitted", 0.3, rid=0, replica=0)
    rec.emit("round", 0.3, replica=0, round_s=0.2, n_active=1,
             crashed=False, rids=[0],
             buckets={"prefill": 0.05, "decode_attention": 0.08,
                      "sampler": 0.01, "host_scheduler": 0.02})
    rec.emit("first_token", 0.35, rid=0)
    rec.emit("round", 0.5, replica=0, round_s=0.0, n_active=1,
             crashed=False, rids=[0])
    rec.emit("finish", 0.5, rid=0, n_tokens=2)
    rec.emit("sent", 0.6, rid=0, committed=0.5)   # delivery after finish
    path = tmp_path / "t.jsonl"
    rec.dump(str(path))

    tr = _load_tool("trace_report")
    events = tr.load(str(path))
    assert tr.rounds_of(events) == {0: 2}       # from the rounds' rids
    assert [e["event"] for e in tr.spans_of(events)[0]] == [
        "queued", "admitted", "first_token", "finish"]
    text = tr.report(events)
    assert "waterfall" in text and "finish" in text
    for b in ("prefill", "decode_attention", "sampler", "host_scheduler"):
        assert b in text
    assert "1 requests" in text
    assert tr.main([str(path), "--limit", "1"]) == 0


# ---------------------------------------------------------------------------
# The inertness proof: obs on == obs off, bit for bit
# ---------------------------------------------------------------------------


def _stream_map(router):
    return {r.rid: (list(r.generated), r.first_token_t, r.finish_t)
            for r in router.completed}


def _run(cls, method, engine, params, cfg, *, paged, obs):
    arrivals = poisson_arrivals(10.0, 2.0, seed=13)
    router = cls(_pool(engine, params, paged=paged),
                 QueueDepthPolicy(max_replicas=2), _reqs(arrivals, cfg),
                 traffic_name="obs", obs=obs)
    return router, getattr(router, method)()


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("driver,method",
                         [(Router, "run"), (EventRouter, "run_events")],
                         ids=["sync", "event"])
def test_obs_on_vs_off_bit_identical(stack, driver, method, paged):
    _, cfg, engine, params = stack
    off, rep_off = _run(driver, method, engine, params, cfg,
                        paged=paged, obs=None)
    obs = Observability(tracer=TraceRecorder())
    on, rep_on = _run(driver, method, engine, params, cfg,
                      paged=paged, obs=obs)
    assert rep_off.summary() == rep_on.summary()
    assert _stream_map(off) == _stream_map(on)

    # with obs on, the counters agree exactly with the report
    c = obs.m_requests
    assert c.value(outcome="completed") == rep_on.n_completed
    assert c.value(outcome="rejected") == rep_on.n_rejected
    assert c.value(outcome="expired") == rep_on.n_expired
    assert obs.m_tokens.value() == sum(
        len(r.generated) for r in on.completed)
    assert obs.m_ttft.count() == len(rep_on.ttft_s)
    assert obs.m_busy_s.value() == pytest.approx(on.pool.busy_seconds())
    assert obs.m_cold_starts.value() == on.pool.n_spawns
    # every completed request traced a full span with ONE terminal
    spans = obs.tracer.spans()
    for r in on.completed:
        names = [e["event"] for e in spans[r.rid]]
        assert names[0] == "queued" and names[-1] == "finish"
        assert sum(n in TERMINAL_EVENTS for n in names) == 1
    # and the scrape the front door serves is valid exposition
    assert lint_prometheus(obs.registry.render()) == []


def test_virtual_clock_traces_are_byte_identical_across_runs(stack):
    _, cfg, engine, params = stack
    dumps = []
    for _ in range(2):
        obs = Observability(tracer=TraceRecorder())
        _run(EventRouter, "run_events", engine, params, cfg,
             paged=False, obs=obs)
        dumps.append(obs.tracer.dumps())
    assert dumps[0] == dumps[1]
    assert len(dumps[0].splitlines()) > 10


# ---------------------------------------------------------------------------
# Subscriber-fault isolation (dense + paged)
# ---------------------------------------------------------------------------


def _drive_batcher(engine, params, cfg, *, paged, on_token=None):
    batcher = ContinuousBatcher(engine, params, n_slots=SLOTS,
                                max_len=MAXLEN, paged=paged, page_size=8,
                                on_token=on_token)
    rng = np.random.default_rng(3)
    for rid in range(5):                 # 5 requests over 2 slots: churn
        batcher.submit(Request(rid, rng.integers(0, cfg.vocab_size,
                                                 PROMPT),
                               max_new_tokens=NEW))
    rounds = 0
    while not batcher.scheduler.idle:
        batcher.step()
        rounds += 1
        assert rounds < 100
    return batcher


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_raising_on_token_subscriber_is_contained(stack, paged):
    _, cfg, engine, params = stack
    base = _drive_batcher(engine, params, cfg, paged=paged)
    want = {r.rid: list(r.generated) for r in base.scheduler.completed}

    seen = []

    def bad_subscriber(req, tok, prefill):
        seen.append((req.rid, tok, prefill))
        raise RuntimeError("subscriber boom")

    b = _drive_batcher(engine, params, cfg, paged=paged,
                       on_token=bad_subscriber)
    got = {r.rid: list(r.generated) for r in b.scheduler.completed}
    assert got == want                       # streams unharmed
    assert len(seen) > 0
    assert b.on_token_errors == len(seen)    # every fault counted
    assert all(s is None for s in b.scheduler.slots)   # rows freed once
    if paged:                                # no leaked/double-freed pages
        assert b.allocator.n_live == 0
        assert b.allocator.n_free == b.allocator.n_pages - 1


# ---------------------------------------------------------------------------
# Readiness + the O(1) scrape
# ---------------------------------------------------------------------------


def test_readiness_false_through_cold_start_window(stack):
    model, cfg, _, params = stack
    cold_engine = Engine(model, RunConfig(cache_pad=8))   # nothing compiled
    pool = _pool(cold_engine, params)
    router = EventRouter(pool, FixedReplicas(n=1))

    r0 = router.readiness()
    assert r0["ok"] is True and r0["ready"] is False      # no replicas
    assert r0["n_replicas"] == 0

    pool.spawn(0.0)
    pool.poll_ready(0.1)                  # inside the 0.3s cold start
    r1 = router.readiness()
    assert r1["n_replicas"] == 1 and r1["n_ready"] == 0
    assert r1["ready"] is False

    pool.poll_ready(0.5)                  # replica up — engine still cold
    r2 = router.readiness()
    assert r2["n_ready"] == 1 and r2["ready"] is False
    assert not cold_engine.warm

    rep = pool.ready()[0]                 # first request compiles a bucket
    rep.batcher.submit(Request(0, np.ones(PROMPT, np.int32),
                               max_new_tokens=1))
    rep.batcher.step()
    assert cold_engine.warm
    assert router.readiness()["ready"] is True


def test_live_stats_is_o1_and_never_calls_report(stack):
    _, cfg, engine, params = stack
    obs = Observability()
    router, rep = _run(EventRouter, "run_events", engine, params, cfg,
                       paged=False, obs=obs)

    def boom():                           # the old hot-path bug: scrape
        raise AssertionError("live_stats called _report()")   # -> report

    router._report = boom
    ls = router.live_stats()
    assert ls["n_completed"] == rep.n_completed
    assert ls["n_rejected"] == rep.n_rejected
    assert ls["n_expired"] == rep.n_expired
    assert ls["n_cancelled"] == 0
    assert ls["tokens_out"] == sum(len(r.generated)
                                   for r in router.completed)
    assert ls["cost_usd"] == pytest.approx(rep.cost_usd, abs=1e-8)
    assert ls["ttft_p50_s"] > 0          # registry bucket-boundary p50


def test_router_attaches_its_observability_to_the_engine(stack):
    """The router hands its registry to the pool's engine, which sets the
    placement gauges at once; without a mesh they read one device holding
    every parameter, and the engine writes no ``layout`` event."""
    model, cfg, _, params = stack
    engine = Engine(model, RunConfig(cache_pad=8))
    obs = Observability(tracer=TraceRecorder())
    router, _ = _run(EventRouter, "run_events", engine, params, cfg,
                     paged=False, obs=obs)
    assert engine.obs is obs
    text = obs.registry.render()
    assert lint_prometheus(text) == []
    assert "repro_engine_mesh_devices 1" in text.splitlines()
    total = sum(l.nbytes for l in jax.tree.leaves(params))
    assert f"repro_engine_param_bytes_per_device {total}" in \
        text.splitlines()
    assert router.completed
    assert not [e for e in obs.tracer.events if e["event"] == "layout"]
