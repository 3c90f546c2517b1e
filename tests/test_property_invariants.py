"""Hypothesis property tests on the system's invariants:
decomposition coverage, cost-model monotonicity/accounting, capacity,
merge exactness, checkpoint round-trips, router arrival/queue laws.
"""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed; property tests skip rather than "
           "breaking collection of the whole suite")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (ArtifactStore, AWSPriceBook, BatchJob,
                        LatencyModel, Orchestrator, OrchestratorConfig,
                        ServerlessFunction, coverage_ok, decompose)
from repro.core.cost_model import TPUPriceBook
from repro.core.job import TaskRecord, Chunk, InvokeOutcome
from repro.data.pipeline import DatasetRef, chunk_ranges
from repro.models.common import MoEConfig
from repro.models.moe import capacity
from repro.router import (ArrivalQueue, EventQueue, QueueConfig,
                          RoundSample, bursty_arrivals, diurnal_arrivals,
                          fit_round_model, poisson_arrivals)
from repro.batch.dag import DONE, PREEMPTED, STATES, TaskDag, TaskSpec
from repro.serving.batching import Request


# ---------------------------------------------------------------------------
# Decomposition
# ---------------------------------------------------------------------------


# no deadline: n=100k at bs=1 builds 100k chunks, and the time that takes
# varies with the host, not with correctness
@given(n=st.integers(1, 100_000), bs=st.integers(1, 5_000))
@settings(deadline=None)
def test_chunks_partition_dataset_exactly(n, bs):
    job = BatchJob("j", DatasetRef("d", n, 1, 1), "", bs)
    chunks = decompose(job)
    assert coverage_ok(chunks, n)
    assert sum(c.n_items for c in chunks) == n
    assert len(chunks) == -(-n // bs)  # ceil


@given(n=st.integers(1, 10_000), bs=st.integers(1, 500))
def test_chunk_ranges_sorted_and_tight(n, bs):
    ranges = chunk_ranges(n, bs)
    assert ranges[0][0] == 0 and ranges[-1][1] == n
    for (s0, e0), (s1, e1) in zip(ranges, ranges[1:]):
        assert e0 == s1 and e0 - s0 == bs  # only the last may be short


# ---------------------------------------------------------------------------
# Cost model (Eq 1 / Eq 2)
# ---------------------------------------------------------------------------


@given(dur=st.floats(0.001, 10_000), ram=st.floats(128, 10_240))
def test_cost_monotone_in_duration_and_ram(dur, ram):
    book = AWSPriceBook()
    c = book.compute_cost(dur, ram)
    assert c >= 0
    assert book.compute_cost(dur * 2, ram) >= c
    assert book.compute_cost(dur, ram * 2) >= c


@given(dur=st.floats(0.0005, 100))
def test_billing_quantum_rounds_up(dur):
    book = AWSPriceBook()
    billed = book.billed_seconds(dur)
    assert billed >= dur - 1e-12
    assert billed - dur <= book.billing_quantum_ms / 1000.0 + 1e-12


@given(durs=st.lists(st.floats(0.01, 900), min_size=1, max_size=50),
       ram=st.floats(128, 3008))
def test_parallel_cost_geq_compute_cost(durs, ram):
    """Eq(1) >= pure compute: requests + transitions only add cost."""
    book = AWSPriceBook()
    tasks = [TaskRecord(Chunk(i, 0, 1), 1, i, 0.0, d,
                        InvokeOutcome(duration_s=d), billed_s=d)
             for i, d in enumerate(durs)]
    total = book.cost_parallel(tasks, ram)
    compute = sum(book.compute_cost(d, ram) for d in durs)
    assert total >= compute
    overhead = total - compute
    expected = (len(durs) * book.per_request
                + (book.base_transitions
                   + book.transitions_per_task * len(durs))
                * book.per_transition)
    assert abs(overhead - expected) < 1e-9


@given(chip_seconds=st.floats(0, 1e9))
def test_tpu_cost_linear(chip_seconds):
    book = TPUPriceBook()
    assert abs(book.cost(chip_seconds) * 2
               - book.cost(2 * chip_seconds)) < 1e-6


# ---------------------------------------------------------------------------
# Conservation: total billed compute ~ constant under decomposition
# ---------------------------------------------------------------------------


@given(bs=st.sampled_from([10, 25, 50, 100, 250]))
@settings(deadline=None, max_examples=5)
def test_compute_seconds_conserved_under_batch_size(bs):
    """The paper's core insight: decomposition changes wall time, not
    total compute-seconds (up to per-invocation overhead)."""
    n = 1000
    per_item = 0.01
    store = ArtifactStore()
    job = BatchJob("j", DatasetRef("d", n, 1, 1), "", bs)
    lat = LatencyModel(cold_start_s=0.0, warm_start_s=0.0,
                       invoke_overhead_s=0.0, result_write_s=0.0,
                       per_item_s=per_item)
    orch = Orchestrator(store, OrchestratorConfig(max_concurrency=1000))
    report = orch.run(job, decompose(job),
                      lambda i: ServerlessFunction(i, store, lat))
    assert abs(report.total_billed_s - n * per_item) < 1e-6


# ---------------------------------------------------------------------------
# MoE capacity
# ---------------------------------------------------------------------------


@given(t=st.integers(1, 100_000), e=st.integers(1, 128),
       k=st.integers(1, 8), cf=st.floats(1.0, 4.0))
def test_capacity_bounds(t, e, k, cf):
    mc = MoEConfig(num_experts=e, top_k=k, expert_ff=8, capacity_factor=cf)
    c = capacity(mc, t)
    assert c >= k                       # a token's k slots always fit
    assert c % 4 == 0 or c == k         # lane-aligned
    assert c * e >= cf * k * t - 4 * e  # total slots cover demand


# ---------------------------------------------------------------------------
# Store / merge
# ---------------------------------------------------------------------------


@given(keys=st.lists(st.text(min_size=1, max_size=20), min_size=1,
                     max_size=20, unique=True))
def test_store_idempotent_first_writer_wins(keys):
    store = ArtifactStore()
    for k in keys:
        assert store.put("k/" + k, b"first", overwrite=False)
        assert not store.put("k/" + k, b"second", overwrite=False)
        assert store.get("k/" + k) == b"first"


# ---------------------------------------------------------------------------
# Router: traffic generators / arrival queue
# ---------------------------------------------------------------------------


@given(rate=st.floats(0.5, 50.0), horizon=st.floats(0.5, 8.0),
       seed=st.integers(0, 2**16))
@settings(deadline=None, max_examples=25)
def test_arrivals_sorted_bounded_deterministic(rate, horizon, seed):
    for gen in (poisson_arrivals, bursty_arrivals, diurnal_arrivals):
        a = gen(rate, horizon, seed)
        assert np.array_equal(a, gen(rate, horizon, seed))
        assert np.all(np.diff(a) >= 0)
        assert a.size == 0 or (a[0] >= 0.0 and a[-1] < horizon)


def _reqs(n):
    return [Request(i, np.ones(2, np.int32), max_new_tokens=1)
            for i in range(n)]


@given(n=st.integers(1, 40), cap=st.integers(1, 40))
@settings(deadline=None, max_examples=30)
def test_queue_fifo_and_admission_cap(n, cap):
    q = ArrivalQueue(QueueConfig(max_depth=cap))
    admitted = [r for r in _reqs(n) if q.submit(r, 0.0)]
    assert len(admitted) == min(n, cap)
    assert q.n_submitted == n and len(q.rejected) == n - len(admitted)
    popped = []
    while (r := q.pop(0.0)) is not None:
        popped.append(r.rid)
    assert popped == [r.rid for r in admitted]  # FIFO, no loss


@given(n=st.integers(2, 20), k=st.integers(1, 10))
@settings(deadline=None, max_examples=30)
def test_queue_requeue_front_preserves_order(n, k):
    """Crash re-queue puts the k lost requests ahead of the waiting
    queue, in their original order, with work reset."""
    q = ArrivalQueue()
    for r in _reqs(n):
        q.submit(r, 0.0)
    k = min(k, n)
    lost = [q.pop(0.0) for _ in range(k)]
    for r in lost:
        r.generated = [1]
    q.requeue(lost)
    order = []
    while (r := q.pop(0.0)) is not None:
        order.append(r.rid)
        assert r.generated == [] or r.rid >= k
    assert order == list(range(n))
    assert q.n_requeued == k


# ---------------------------------------------------------------------------
# Router: event-loop laws (queue.py priority classes + exactly-once
# expiry, events.py EventQueue ordering — tests/test_event_router.py
# pins the deterministic cases)
# ---------------------------------------------------------------------------


@given(ts=st.lists(st.sampled_from([0.0, 0.25, 0.5, 1.0, 2.0]),
                   min_size=1, max_size=40))
def test_event_queue_pops_by_time_then_push_order(ts):
    """The determinism anchor of the event-driven driver: events pop
    ordered by (t, push order) — equal-time events keep FIFO order."""
    eq = EventQueue()
    for i, t in enumerate(ts):
        eq.push(t, "e", i)
    out = [eq.pop() for _ in range(len(ts))]
    assert not eq and eq.peek_t() is None
    expected = sorted(enumerate(ts), key=lambda p: (p[1], p[0]))
    assert [(t, payload) for t, _, payload in out] == [
        (t, i) for i, t in expected]


@given(pris=st.lists(st.integers(0, 3), min_size=1, max_size=40))
@settings(deadline=None, max_examples=40)
def test_queue_fifo_within_priority_class(pris):
    """Lower class numbers dispatch first; WITHIN a class, strict
    submission order (== a stable sort by priority)."""
    q = ArrivalQueue()
    reqs = [Request(i, np.ones(2, np.int32), max_new_tokens=1, priority=p)
            for i, p in enumerate(pris)]
    for r in reqs:
        q.submit(r, 0.0)
    popped = []
    while (r := q.pop(0.0)) is not None:
        popped.append(r.rid)
    assert popped == [r.rid for r in
                      sorted(reqs, key=lambda r: r.priority)]


@given(data=st.data())
@settings(deadline=None, max_examples=50)
def test_expiry_exactly_once_under_random_interleavings(data):
    """Any interleaving of admit / pop / crash-requeue / clock-advance:
    every admitted request ends in EXACTLY one terminal partition
    (served or expired), the expired list never double-counts, and
    requeue never resurrects a request that already expired."""
    q = ArrivalQueue(QueueConfig(default_deadline_s=1.0))
    now = 0.0
    admitted, inflight = [], []
    for _ in range(data.draw(st.integers(1, 40), label="n_ops")):
        op = data.draw(st.sampled_from(["admit", "pop", "requeue",
                                        "advance"]))
        if op == "admit":
            r = Request(len(admitted), np.ones(2, np.int32),
                        max_new_tokens=1,
                        priority=data.draw(st.integers(0, 2)))
            q.submit(r, now)
            admitted.append(r)
        elif op == "pop":
            r = q.pop(now)
            if r is not None:
                inflight.append(r)
        elif op == "requeue" and inflight:
            k = data.draw(st.integers(1, len(inflight)))
            lost, inflight = inflight[:k], inflight[k:]
            q.requeue(lost, now)
        elif op == "advance":
            now += data.draw(st.sampled_from([0.3, 0.7, 1.1]))
    served = list(inflight)
    while (r := q.pop(now)) is not None:
        served.append(r)
    assert q.depth == 0
    exp_ids = [id(r) for r in q.expired]
    assert len(exp_ids) == len(set(exp_ids))        # exactly-once
    for r in q.expired:                              # never resurrected
        assert all(s is not r for s in served)
    # partition: served + expired is exactly the admitted set
    assert sorted(map(id, served + q.expired)) == sorted(map(id, admitted))
    # a served request really was within its deadline when dispatched
    for r in served:
        assert id(r) not in q._expired_ids


# ---------------------------------------------------------------------------
# Router: round-time calibration laws
# ---------------------------------------------------------------------------

# three designs of full rank: (prefill_tokens, active_slots) anchors
_CAL_ANCHORS = [(0, 1), (0, 8), (256, 0)]


@given(overhead=st.floats(0.0, 0.05), per_item=st.floats(1e-4, 0.1),
       factor=st.floats(0.01, 1.0), n_extra=st.integers(0, 12),
       seed=st.integers(0, 2**16))
@settings(deadline=None, max_examples=40)
def test_calibration_fit_error_non_increasing_with_rows(
        overhead, per_item, factor, n_extra, seed):
    """More measured rows never degrade the fit: with consistent samples
    (all drawn from one ground-truth round model), the full-set error of
    the least-squares fit is non-increasing as rows are added, and a
    full-rank sample set recovers the model exactly."""
    def truth(p, a):
        return overhead + per_item * (p * factor + a)

    rng = np.random.default_rng(seed)
    pts = list(_CAL_ANCHORS) + [(int(rng.integers(0, 512)),
                                 int(rng.integers(0, 16)))
                                for _ in range(n_extra)]
    samples = [RoundSample(p, a, truth(p, a)) for p, a in pts]
    errs = []
    for k in range(len(_CAL_ANCHORS), len(samples) + 1):
        cal = fit_round_model(samples[:k])
        errs.append(max(abs(cal.round_seconds(p, a) - truth(p, a))
                        for p, a in pts))
    for e0, e1 in zip(errs, errs[1:]):
        assert e1 <= e0 + 1e-9
    assert errs[-1] <= 1e-6          # consistent rows -> exact recovery


@given(overhead=st.floats(0.0, 0.05), per_item=st.floats(1e-4, 0.1),
       factor=st.floats(0.01, 1.0))
@settings(deadline=None, max_examples=25)
def test_calibration_recovers_and_is_nonnegative(overhead, per_item,
                                                 factor):
    """Exact parameter recovery from noise-free full-rank samples, and
    the fitted constants are never negative (latencies can't be)."""
    samples = [RoundSample(p, a, overhead + per_item * (p * factor + a))
               for p, a in _CAL_ANCHORS + [(128, 4)]]
    cal = fit_round_model(samples)
    assert cal.round_overhead_s >= 0.0
    assert cal.per_item_s >= 0.0
    assert cal.prefill_token_factor >= 0.0
    assert abs(cal.round_overhead_s - overhead) < 1e-7
    assert abs(cal.per_item_s - per_item) < 1e-7
    assert cal.rmse_s < 1e-7


# ---------------------------------------------------------------------------
# Paged KV cache: PageAllocator laws
# ---------------------------------------------------------------------------

from collections import Counter  # noqa: E402

from repro.serving.paged import PageAllocator, PagesExhausted  # noqa: E402


def _check_allocator_laws(alloc: PageAllocator):
    """The conservation/ownership invariants every op sequence preserves:

    * page 0 (null) is never owned, never free-listed, never reclaimable;
    * every physical page 1..n-1 is in EXACTLY one of {live, free list,
      reclaim pool} — nothing leaks, nothing double-books;
    * refcount(p) == number of rows holding p (and 0 off-row);
    * a row's pages are distinct (one physical page per logical page).
    """
    held = Counter(p for pages in alloc.rows.values() for p in pages)
    assert 0 not in held
    assert 0 not in alloc.free_list and 0 not in alloc.reclaimable
    for pages in alloc.rows.values():
        assert len(set(pages)) == len(pages)
    live, free = set(held), set(alloc.free_list)
    rec = set(alloc.reclaimable)
    assert len(free) == len(alloc.free_list)      # free list has no dupes
    assert not (live & free) and not (live & rec) and not (free & rec)
    assert live | free | rec == set(range(1, alloc.n_pages))
    for p in range(alloc.n_pages):
        assert alloc.refcounts[p] == held.get(p, 0)
    assert alloc.n_free == len(free) + len(rec)
    assert alloc.n_live == len(live)


@given(data=st.data())
@settings(deadline=None, max_examples=60)
def test_page_allocator_laws_hold_under_any_op_sequence(data):
    """admit / free / fork / writable_page in any interleaving keep the
    conservation + refcount laws; failures (PagesExhausted, over-long
    requests, occupied rows) must leave state untouched too."""
    n_pages = data.draw(st.integers(3, 24), label="n_pages")
    ps = data.draw(st.integers(1, 4), label="page_size")
    max_pages = data.draw(st.integers(1, 6), label="max_pages")
    alloc = PageAllocator(n_pages, ps, max_pages)
    _check_allocator_laws(alloc)
    next_row = 0
    for _ in range(data.draw(st.integers(1, 25), label="n_ops")):
        rows = sorted(alloc.rows)
        op = data.draw(st.sampled_from(
            ["admit", "free", "fork", "cow"] if rows else ["admit"]))
        if op == "admit":
            plen = data.draw(st.integers(1, max_pages * ps + 2))
            mnt = data.draw(st.integers(0, 3))
            prompt = data.draw(st.lists(st.integers(0, 2), min_size=plen,
                                        max_size=plen))
            try:
                plan = alloc.admit(next_row, prompt, mnt)
                assert len(plan.suffix) > 0     # last token never matched
                assert plan.start_len == plan.n_shared * ps
                next_row += 1
            except (PagesExhausted, ValueError):
                pass
        elif op == "free":
            row = data.draw(st.sampled_from(rows))
            before = alloc.n_free
            freed = alloc.free(row)
            assert alloc.n_free == before + len(freed)
        elif op == "fork":
            src = data.draw(st.sampled_from(rows))
            try:
                assert alloc.fork(src, next_row) == alloc.rows[src]
                next_row += 1
            except ValueError:
                pass
        elif op == "cow":
            row = data.draw(st.sampled_from(rows))
            span = len(alloc.rows[row]) * ps
            pos = data.draw(st.integers(0, span - 1))
            try:
                alloc.writable_page(row, pos)
                # post-condition: the write target is exclusively owned
                assert alloc.refcounts[
                    alloc.rows[row][pos // ps]] == 1
            except PagesExhausted:
                pass
        _check_allocator_laws(alloc)


@given(seed=st.integers(0, 2**31 - 1),
       lens=st.lists(st.integers(0, 127), min_size=1, max_size=3))
@settings(deadline=None, max_examples=8)
def test_paged_kernel_parity_random_lengths(seed, lens):
    """Interpret-mode paged kernel == dense ragged kernel over the
    gathered view at ARBITRARY per-row lengths (hypothesis picks them;
    0 and S_max-1 are reachable draws)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention import (decode_attention,
                                                gather_pages,
                                                paged_decode_attention)

    b, p, ps, pmax, h, kv, d = len(lens), 12, 64, 2, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, d))
    k_pages = jax.random.normal(ks[1], (p, ps, kv, d))
    v_pages = jax.random.normal(ks[2], (p, ps, kv, d))
    perm = np.random.default_rng(seed).permutation(np.arange(1, p))
    table = jnp.asarray(perm[:b * pmax].reshape(b, pmax), jnp.int32)
    lengths = jnp.asarray(lens, jnp.int32)
    out = paged_decode_attention(q, k_pages, v_pages, lengths, table,
                                 interpret=True)
    ref = decode_attention(q, gather_pages(k_pages, table),
                           gather_pages(v_pages, table), lengths,
                           block_t=ps, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

# ---------------------------------------------------------------------------
# Observability: registry / trace / outcome-partition laws
# ---------------------------------------------------------------------------

import functools  # noqa: E402

from repro.obs import (Histogram, Observability, SPAN_EVENTS,  # noqa: E402
                       TERMINAL_EVENTS, TraceRecorder, log_buckets)


@given(vals=st.lists(st.floats(1e-6, 1e3, allow_nan=False), max_size=200),
       per_decade=st.integers(1, 4))
@settings(deadline=None, max_examples=60)
def test_histogram_buckets_sum_to_count_and_cumulative_monotone(
        vals, per_decade):
    """The exposition-format laws every scrape relies on: cumulative
    bucket counts are monotone non-decreasing, the +Inf bucket equals
    the observe count, per-bucket deltas sum back to the count, and
    the running sum is the exact left-fold of the observed values."""
    h = Histogram("h", "x", buckets=log_buckets(1e-4, 100.0, per_decade))
    acc = 0.0
    for v in vals:
        h.observe(v)
        acc += v
    cum = [c for _, c in h.cumulative()]
    assert cum == sorted(cum)                       # monotone
    assert cum[-1] == h.count() == len(vals)        # +Inf == count
    deltas = [cum[0]] + [b - a for a, b in zip(cum, cum[1:])]
    assert all(d >= 0 for d in deltas)
    assert sum(deltas) == len(vals)                 # partition exactly
    assert h.sum() == acc                           # same fold order
    if vals:
        q = h.quantile(0.5)
        assert h.bounds[0] <= q <= h.bounds[-1]


@given(data=st.data())
@settings(deadline=None, max_examples=50)
def test_trace_spans_monotone_with_single_terminal(data):
    """Under ANY interleaving of per-request lifecycles on a global
    non-decreasing clock (the only way RouterCore ever emits), each
    request's span has non-decreasing timestamps, at most one terminal
    event which comes last, and replaying the events byte-reproduces
    the JSONL (the virtual-clock determinism contract)."""
    LIFE = ("queued", "admitted", "prefill", "first_token", "finish")
    rec = TraceRecorder()
    n = data.draw(st.integers(1, 8), label="n_requests")
    stage = {rid: 0 for rid in range(n)}
    t = 0.0
    for _ in range(data.draw(st.integers(1, 60), label="n_ops")):
        rid = data.draw(st.integers(0, n - 1))
        t += data.draw(st.sampled_from([0.0, 0.1, 0.5]))
        if stage[rid] >= len(LIFE):
            # delivery may follow finish; it rides beside the span
            rec.emit("sent", t, rid=rid, committed=t)
            continue
        ev = LIFE[stage[rid]]
        if ev == "first_token" and data.draw(st.booleans()):
            rec.emit("round", t, replica=0, rids=[rid])  # extra rounds ok
            continue
        rec.emit(ev, t, rid=rid)
        stage[rid] += 1
    for rid, span in rec.spans().items():
        assert all(e["event"] in SPAN_EVENTS for e in span)
        ts = [e["t"] for e in span]
        assert ts == sorted(ts)                      # monotone per span
        terms = [e for e in span if e["event"] in TERMINAL_EVENTS]
        assert len(terms) <= 1
        if terms:
            assert span[-1] is terms[0]
        assert rec.terminal(rid) == (terms[0]["event"] if terms else None)
    replay = TraceRecorder()
    for e in rec.events:
        replay.emit(e["event"], e["t"], rid=e.get("rid"),
                    **{k: v for k, v in e.items()
                       if k not in ("event", "t", "rid")})
    assert replay.dumps() == rec.dumps()


@functools.lru_cache(maxsize=1)
def _obs_serving_stack():
    import jax
    from repro import configs
    from repro.models import RunConfig, build
    from repro.serving import Engine

    cfg = configs.smoke("qwen2-7b")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, Engine(model, RunConfig(cache_pad=8)), params


@given(seed=st.integers(0, 2**16), depth=st.integers(2, 12),
       deadline_s=st.sampled_from([0.6, 1.0, 30.0]))
@settings(deadline=None, max_examples=6)
def test_terminal_outcomes_partition_exactly_as_router_report(
        seed, depth, deadline_s):
    """Real router runs: the ``repro_requests_total`` outcome partition
    equals RouterReport's terminal counts exactly, covers every
    submitted request, and the trace gives each rid exactly one
    terminal event."""
    from repro.core import FaultInjector, LatencyModel
    from repro.router import (QueueDepthPolicy, ReplicaConfig,
                              ReplicaPool, Router, make_requests,
                              poisson_arrivals)

    cfg, engine, params = _obs_serving_stack()
    arrivals = poisson_arrivals(12.0, 1.5, seed)
    obs = Observability(tracer=TraceRecorder())
    pool = ReplicaPool(engine, params,
                       ReplicaConfig(n_slots=2, max_len=16),
                       lat=LatencyModel(cold_start_s=0.3, per_item_s=0.05),
                       injector=FaultInjector())
    reqs = make_requests(arrivals, prompt_len=8, max_new_tokens=4,
                         vocab=cfg.vocab_size, seed=0,
                         deadline_s=deadline_s)
    router = Router(pool, QueueDepthPolicy(max_replicas=2), reqs,
                    queue_cfg=QueueConfig(max_depth=depth,
                                          default_deadline_s=deadline_s),
                    traffic_name="law", obs=obs)
    rep = router.run()

    c = obs.m_requests
    assert c.value(outcome="completed") == rep.n_completed
    assert c.value(outcome="rejected") == rep.n_rejected
    assert c.value(outcome="expired") == rep.n_expired
    assert c.value(outcome="cancelled") == 0
    assert (rep.n_completed + rep.n_rejected + rep.n_expired
            == arrivals.size)                        # full partition
    spans = obs.tracer.spans()
    assert sorted(spans) == list(range(arrivals.size))
    for span in spans.values():
        assert sum(e["event"] in TERMINAL_EVENTS for e in span) == 1


# ---------------------------------------------------------------------------
# Batch-DAG scheduler laws (repro.batch.dag)
# ---------------------------------------------------------------------------


def _random_dag(data, n):
    """Random acyclic graph: each task may depend only on earlier ones,
    so construction never raises — the laws below exercise execution."""
    tasks = []
    for i in range(n):
        deps = ()
        if i:
            k = data.draw(st.integers(0, min(i, 3)), label=f"ndeps[{i}]")
            deps = tuple(
                f"t{d}" for d in data.draw(
                    st.lists(st.integers(0, i - 1), min_size=k,
                             max_size=k, unique=True),
                    label=f"deps[{i}]"))
        tasks.append(TaskSpec(f"t{i}", "stage", deps=deps))
    return TaskDag(tasks, retry_backoff_s=0.25)


@given(data=st.data())
@settings(deadline=None, max_examples=50)
def test_dag_topo_partition_and_exactly_once_laws(data):
    """Three laws under RANDOM ready-set pops and preemption
    interleavings: (1) the five scheduler states always partition the
    task set; (2) execution order is topological — every dependency is
    DONE before its dependents complete, and the completion sequence
    linearizes the DAG; (3) retries never duplicate a reduce
    contribution — the first-writer-wins store accepts exactly one
    commit per task, no matter how kills interleave."""
    n = data.draw(st.integers(1, 12), label="n")
    dag = _random_dag(data, n)
    store = ArtifactStore()
    now, accepted, duplicates = 0.0, 0, 0
    completed_order = []
    for step in range(10_000):
        counts = dag.counts()
        assert set(counts) == set(STATES)
        assert sum(counts.values()) == n        # (1) partition conserved
        if dag.all_done:
            break
        ready = dag.ready(now)
        if not ready:
            nxt = dag.next_retry_t()            # only retries can stall
            assert nxt is not None and counts[PREEMPTED] > 0
            now = max(now, nxt)
            continue
        pick = data.draw(
            st.sampled_from(sorted(t.task_id for t in ready)),
            label="pick")
        dag.start(pick, now)
        if (dag.tasks[pick].preemptions < 2
                and data.draw(st.booleans(), label="kill")):
            dag.preempt(pick, now)              # random kill mid-task
            now += 1e-3
            continue
        assert all(dag.tasks[d].state == DONE    # (2) deps done first
                   for d in dag.tasks[pick].deps)
        if store.put(pick, b"contribution", overwrite=False):
            accepted += 1
        else:
            duplicates += 1
        dag.complete(pick, now)
        completed_order.append(pick)
        now += 1e-3
    assert dag.all_done
    assert accepted == n and duplicates == 0    # (3) exactly-once
    pos = {tid: i for i, tid in enumerate(completed_order)}
    for t in dag.tasks.values():
        assert t.attempts == t.preemptions + 1  # resume, never restart
        for d in t.deps:
            assert pos[d] < pos[t.task_id]      # (2) topological order
