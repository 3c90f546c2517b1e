"""Event-driven front door: parity proof + streaming HTTP smoke.

The tentpole claims, pinned:

  * PARITY — ``EventRouter.run_events()`` (virtual event queue) and
    ``Router.run()`` (synchronous rounds) are thin drivers over one
    ``RouterCore``, so at the same seed they produce BIT-IDENTICAL
    per-request token streams, first-token/finish timestamps, and
    report summaries — across traffic shapes, dense and paged caches,
    and under injected crashes. The event path also reuses the sync
    path's compiled executables (compile_count flat) and keeps exactly
    one decode dispatch per scheduling round.
  * TTFT AT THE EVENT — first tokens are stamped mid-round at their
    prefill event (``metrics.record_first_token``, exactly once), not
    at the round boundary; a crash discards the doomed round's events
    so no stamp lands, and a stamp earned on an earlier round survives
    ``reset_for_retry`` (the client saw that token).
  * HTTP FRONT DOOR — a stdlib-asyncio server streams NDJSON token
    chunks to 8 concurrent clients with REAL (measured) TTFT/TPOT; a
    mid-flight disconnect cancels the request and frees its cache row
    without killing the round; requests the cache can never hold end
    their streams cleanly instead of hanging the client. Each token is
    handed to its socket in the round that commits it, and a client
    that stops reading is backed up without stalling the others.

Async/event-loop tests run under a per-test ``signal.alarm`` guard so
a stuck loop fails loudly instead of hanging the suite.
"""
import asyncio
import json
import signal
import socket

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import FaultInjector, LatencyModel
from repro.models import RunConfig, build
from repro.router import (ArrivalQueue, EventQueue, EventRouter,
                          FixedReplicas, HttpFrontDoor, QueueConfig,
                          QueueDepthPolicy, ReplicaConfig, ReplicaPool,
                          Router, VirtualClock, WallClock, bursty_arrivals,
                          diurnal_arrivals, make_requests, poisson_arrivals)
from repro.obs import Observability, TraceRecorder
from repro.router.metrics import record_first_token
from repro.serving import Engine, Request

PROMPT, NEW, SLOTS, MAXLEN = 8, 4, 2, 16
LAT = LatencyModel(cold_start_s=0.3, per_item_s=0.05)
WALL_LAT = LatencyModel(cold_start_s=0.01, per_item_s=None)

TRAFFIC_GENS = {"poisson": poisson_arrivals, "bursty": bursty_arrivals,
                "diurnal": diurnal_arrivals}


@pytest.fixture(autouse=True)
def per_test_timeout():
    """Hard per-test deadline: a wedged event loop (missed wake, stuck
    chunked read) raises instead of hanging CI."""
    def on_alarm(signum, frame):
        raise TimeoutError("test exceeded the 180s per-test guard")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(180)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="module")
def stack():
    cfg = configs.smoke("qwen2-7b")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, RunConfig(cache_pad=8))
    return engine, params, cfg


def _pool(engine, params, *, paged=False, injector=None, lat=LAT,
          max_len=MAXLEN, n_slots=SLOTS):
    return ReplicaPool(engine, params,
                       ReplicaConfig(n_slots=n_slots, max_len=max_len,
                                     paged=paged, page_size=8),
                       lat=lat, injector=injector or FaultInjector())


def _reqs(arrivals, cfg):
    return make_requests(arrivals, prompt_len=PROMPT, max_new_tokens=NEW,
                         vocab=cfg.vocab_size, seed=0)


def _req(rid, **kw):
    return Request(rid, np.ones(4, np.int32), max_new_tokens=2, **kw)


# ---------------------------------------------------------------------------
# Event primitives: clocks + event queue
# ---------------------------------------------------------------------------


def test_event_queue_orders_by_time_then_push_order():
    eq = EventQueue()
    eq.push(1.0, "a", 1)
    eq.push(0.5, "b", 2)
    eq.push(1.0, "c", 3)
    eq.push(0.5, "d", 4)
    assert len(eq) == 4 and eq.peek_t() == 0.5
    assert [eq.pop() for _ in range(4)] == [
        (0.5, "b", 2), (0.5, "d", 4),   # FIFO tie-break at equal t
        (1.0, "a", 1), (1.0, "c", 3)]
    assert not eq and eq.peek_t() is None


def test_virtual_clock_rejects_backwards_jumps():
    clk = VirtualClock()
    clk.advance_to(2.0)
    assert clk.now() == 2.0
    with pytest.raises(ValueError, match="backwards"):
        clk.advance_to(1.0)


def test_wall_clock_advances_itself():
    clk = WallClock()
    assert not clk.virtual
    t = clk.now()
    clk.advance_to(0.0)          # no-op, never goes backwards
    assert clk.now() >= t >= 0.0


def test_wall_clock_requires_measured_time_model(stack):
    engine, params, _ = stack
    with pytest.raises(ValueError, match="measures time"):
        EventRouter(_pool(engine, params, lat=LAT),   # modeled per_item_s
                    FixedReplicas(n=1), clock=WallClock())


def test_serve_requires_wall_clock(stack):
    engine, params, _ = stack
    router = EventRouter(_pool(engine, params, lat=WALL_LAT),
                         FixedReplicas(n=1))          # virtual by default
    with pytest.raises(RuntimeError, match="wall-clock"):
        asyncio.run(router.serve())


# ---------------------------------------------------------------------------
# Priority classes (deterministic pins; the laws live in
# test_property_invariants.py)
# ---------------------------------------------------------------------------


def test_queue_priority_classes_dispatch_low_first_fifo_within():
    q = ArrivalQueue()
    for pri, rid in [(2, 0), (0, 1), (1, 2), (0, 3), (2, 4), (1, 5)]:
        q.submit(_req(rid, priority=pri), 0.0)
    popped = []
    while (r := q.pop(0.0)) is not None:
        popped.append(r.rid)
    assert popped == [1, 3, 2, 5, 0, 4]


def test_queue_requeue_respects_priority_class_fronts():
    q = ArrivalQueue()
    q.submit(_req(0, priority=1), 0.0)
    q.submit(_req(1, priority=0), 0.0)
    lost = q.pop(0.0)            # rid 1 (class 0) dispatched, then lost
    q.requeue([lost], 0.0)
    assert q.pop(0.0).rid == 1   # back at the front of ITS class
    assert q.pop(0.0).rid == 0


def test_queue_requeue_never_resurrects_expired():
    q = ArrivalQueue(QueueConfig(default_deadline_s=1.0))
    q.submit(_req(0), 0.0)
    r = q.pop(0.0)
    q.requeue([r], 5.0)          # deadline long gone -> expired, once
    assert [x.rid for x in q.expired] == [0]
    assert q.n_requeued == 0
    q.requeue([r], 6.0)          # second crash re-sees it: skipped
    assert len(q.expired) == 1 and q.depth == 0
    assert q.pop(6.0) is None


def test_queue_cancel_removes_by_identity():
    q = ArrivalQueue()
    a, b = _req(0), _req(0)      # same rid, different objects
    q.submit(a, 0.0)
    q.submit(b, 0.0)
    assert q.cancel(b)
    assert not q.cancel(b)       # already gone
    assert q.pop(0.0) is a and q.depth == 0


# ---------------------------------------------------------------------------
# TTFT at the first-token event (satellite: the round-boundary bug)
# ---------------------------------------------------------------------------


def test_record_first_token_stamps_exactly_once():
    r = _req(0, arrival_t=0.0)
    assert record_first_token(r, 0.5)
    assert not record_first_token(r, 9.9)    # second event never moves it
    assert r.first_token_t == 0.5
    r.generated = [1, 2]
    r.reset_for_retry()                      # crash path keeps the stamp
    assert r.first_token_t == 0.5
    assert not record_first_token(r, 9.9)    # re-serve must not re-stamp
    assert r.first_token_t == 0.5


def test_ttft_stamped_mid_round_not_at_boundary(stack):
    """Two requests admitted into one round: first tokens land at their
    serial prefill offsets (0.05 s/prompt at per_item 0.05 x factor
    0.125 x 8 tokens), strictly BEFORE the 0.2s round boundary — the
    regression the old round-boundary stamping would fail."""
    engine, params, cfg = stack
    router = Router(_pool(engine, params), FixedReplicas(n=1),
                    _reqs(np.zeros(2), cfg), traffic_name="test")
    report = router.run()
    assert report.n_completed == 2
    # cold start 0.3 -> round 1 admits both: prefill events at +0.05/+0.10
    assert sorted(report.ttft_s) == pytest.approx([0.35, 0.40])
    boundary = 0.3 + 0.05 * (2 * PROMPT * 0.125 + 2)   # t0 + round_s
    for r in router.completed:
        assert r.arrival_t < r.first_token_t < boundary <= r.finish_t


def test_crash_discards_round_events_and_stamps_after_requeue(stack):
    """Crash -> requeue -> first token: the doomed round's events are
    discarded (no stamp), so retried requests earn their stamp on the
    re-serve — exactly once, after the crash."""
    engine, params, cfg = stack
    arrivals = poisson_arrivals(6.0, 2.0, seed=3)
    router = Router(_pool(engine, params,
                          injector=FaultInjector(seed=5, crash_prob=1.0,
                                                 max_crashes=1)),
                    FixedReplicas(n=1), _reqs(arrivals, cfg),
                    traffic_name="test")
    report = router.run()
    assert report.n_crashes == 1
    assert report.n_completed == arrivals.size
    crash_t = next(e["t"] for e in router.events if e["kind"] == "crash")
    retried = [r for r in router.completed if r.n_retries >= 1]
    assert retried
    for r in router.completed:
        assert r.first_token_t is not None
        assert r.arrival_t <= r.first_token_t <= r.finish_t
    for r in retried:
        # nothing streamed from the crashed round -> stamp is post-crash
        assert r.first_token_t >= crash_t - 1e-9
    assert len(report.ttft_s) == report.n_completed


# ---------------------------------------------------------------------------
# Parity: one event core, two drivers, bit-identical runs
# ---------------------------------------------------------------------------


def _stream_map(router):
    return {r.rid: (list(r.generated), r.first_token_t, r.finish_t)
            for r in router.completed}


def _assert_parity(sync, event, rep_s, rep_e):
    assert rep_s.summary() == rep_e.summary()
    ms, me = _stream_map(sync), _stream_map(event)
    assert sorted(ms) == sorted(me)
    for rid in ms:
        assert ms[rid] == me[rid], f"rid {rid} diverged"
    for router in (sync, event):
        for r in router.pool.replicas:
            if r.batcher.rounds:
                assert r.batcher.decode_dispatches == r.batcher.rounds, (
                    "continuous batching invariant: one decode dispatch "
                    "per scheduling round")


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("traffic", sorted(TRAFFIC_GENS))
def test_event_and_sync_paths_bit_identical(stack, traffic, paged):
    engine, params, cfg = stack
    arrivals = TRAFFIC_GENS[traffic](10.0, 2.5, seed=9)
    assert arrivals.size > 0
    policy = QueueDepthPolicy(max_replicas=3)
    sync = Router(_pool(engine, params, paged=paged), policy,
                  _reqs(arrivals, cfg), traffic_name=traffic)
    rep_s = sync.run()
    compiles = engine.compile_count
    event = EventRouter(_pool(engine, params, paged=paged), policy,
                        _reqs(arrivals, cfg), traffic_name=traffic)
    rep_e = event.run_events()
    # the event path replays the sync path's exact executable buckets
    assert engine.compile_count == compiles
    assert rep_e.n_completed == arrivals.size
    _assert_parity(sync, event, rep_s, rep_e)


def test_parity_holds_under_injected_crashes(stack):
    engine, params, cfg = stack
    arrivals = poisson_arrivals(8.0, 2.0, seed=11)

    def run(cls, method):
        router = cls(_pool(engine, params,
                           injector=FaultInjector(seed=5, crash_prob=1.0,
                                                  max_crashes=1)),
                     QueueDepthPolicy(max_replicas=2),
                     _reqs(arrivals, cfg), traffic_name="crash")
        return router, getattr(router, method)()

    sync, rep_s = run(Router, "run")
    event, rep_e = run(EventRouter, "run_events")
    assert rep_s.n_crashes == rep_e.n_crashes == 1
    assert rep_s.n_requeued >= 1
    _assert_parity(sync, event, rep_s, rep_e)


def test_parity_with_deadlines_and_admission_cap(stack):
    """Terminal outcomes (rejected, expired) land identically too."""
    engine, params, cfg = stack
    burst = np.zeros(10)

    def run(cls, method):
        reqs = make_requests(burst, prompt_len=PROMPT, max_new_tokens=NEW,
                             vocab=cfg.vocab_size, seed=0, deadline_s=0.8)
        router = cls(_pool(engine, params), FixedReplicas(n=1), reqs,
                     queue_cfg=QueueConfig(max_depth=6,
                                           default_deadline_s=0.8),
                     traffic_name="slo")
        return router, getattr(router, method)()

    sync, rep_s = run(Router, "run")
    event, rep_e = run(EventRouter, "run_events")
    assert rep_s.n_rejected > 0 or rep_s.n_expired > 0
    _assert_parity(sync, event, rep_s, rep_e)


# ---------------------------------------------------------------------------
# HTTP front door (wall clock, measured TTFT/TPOT)
# ---------------------------------------------------------------------------


async def _generate(port, i, n_new=5, disconnect_after=None):
    """One streaming client: returns the decoded NDJSON chunks. When
    ``disconnect_after`` is set, hangs up after that many chunks."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps({"prompt": [1 + (i % 7)] * PROMPT,
                       "max_new_tokens": n_new})
    writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n{body}").encode())
    await writer.drain()
    status = await reader.readline()
    assert b"200" in status, status
    while (await reader.readline()) not in (b"\r\n", b"\n"):
        pass
    chunks = []
    while (chunk := await _read_chunk(reader)) is not None:
        chunks.append(chunk)
        if disconnect_after is not None and len(chunks) >= disconnect_after:
            break
    writer.close()
    return chunks


async def _read_chunk(reader):
    """One NDJSON chunk of a chunked body, or None at the terminator."""
    size = int((await reader.readline()).strip() or b"0", 16)
    if size == 0:
        return None
    chunk = json.loads(await reader.readexactly(size))
    await reader.readexactly(2)              # chunk trailer CRLF
    return chunk


async def _get(port, path, parse=json.loads):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    headers = {}
    while (h := await reader.readline()) not in (b"\r\n", b"\n", b""):
        k, _, v = h.decode().partition(":")
        headers[k.strip().lower()] = v.strip()
    body = await reader.readexactly(int(headers["content-length"]))
    writer.close()
    return status, parse(body)


def _door(engine, params, obs=None, **pool_kw):
    router = EventRouter(_pool(engine, params, lat=WALL_LAT, n_slots=4,
                               **pool_kw),
                         QueueDepthPolicy(max_replicas=2),
                         clock=WallClock(), traffic_name="http", obs=obs)
    return router, HttpFrontDoor(router, port=0)


def test_http_streams_eight_concurrent_clients(stack):
    engine, params, _ = stack
    N_CLIENTS, N_NEW = 8, 5

    async def main():
        router, door = _door(engine, params)
        await door.start()
        status, health = await _get(door.port, "/healthz")
        assert status == 200 and health["ok"] is True
        assert set(health) == {"ok", "ready", "n_replicas", "n_ready"}
        streams = await asyncio.gather(
            *(_generate(door.port, i, n_new=N_NEW)
              for i in range(N_CLIENTS)))
        status, stats = await _get(door.port, "/metrics.json")
        assert status == 200 and stats["n_completed"] == N_CLIENTS
        # once requests have flowed, the engine is warm -> door is ready
        status, health = await _get(door.port, "/healthz")
        assert status == 200 and health["ready"] is True
        assert (await _get(door.port, "/nope"))[0] == 404
        await door.close()
        return router, streams

    router, streams = asyncio.run(main())
    for chunks in streams:
        toks, end = chunks[:-1], chunks[-1]
        # the full token stream arrived, in order, prefill marked once
        assert len(toks) == N_NEW
        assert [c["prefill"] for c in toks] == [True] + [False] * (N_NEW - 1)
        assert [c["done"] for c in toks] == [False] * (N_NEW - 1) + [True]
        assert all(c0["t"] <= c1["t"] for c0, c1 in zip(toks, toks[1:]))
        # end chunk carries MEASURED first-token latency
        assert end["event"] == "end" and end["done"]
        assert end["n_tokens"] == N_NEW and end["ttft_s"] > 0
    rep = router.report()
    assert rep.time_model == "measured"
    assert rep.n_completed == N_CLIENTS and rep.n_cancelled == 0
    assert len(rep.ttft_s) == N_CLIENTS and all(t > 0 for t in rep.ttft_s)
    assert len(rep.tpot_s) == N_CLIENTS and all(t > 0 for t in rep.tpot_s)


def test_http_disconnect_cancels_and_frees_row_mid_round(stack):
    """A client hanging up mid-stream cancels its request and frees the
    cache row; the concurrent client in the SAME rounds still completes
    its full stream."""
    engine, params, _ = stack

    async def main():
        router, door = _door(engine, params, max_len=48)
        await door.start()
        long_c, short_c = await asyncio.gather(
            _generate(door.port, 0, n_new=40, disconnect_after=2),
            _generate(door.port, 1, n_new=6))
        await asyncio.sleep(0.3)       # let the EOF watchdog cancel
        await door.close()
        return router, long_c, short_c

    router, long_c, short_c = asyncio.run(main())
    assert len(long_c) == 2            # hung up after two tokens
    assert len(short_c) == 7 and short_c[-1]["event"] == "end"
    assert short_c[-1]["n_tokens"] == 6 and short_c[-1]["done"]
    rep = router.report()
    assert rep.n_cancelled == 1 and rep.n_completed == 1
    for r in router.pool.replicas:     # the cancelled row was freed
        assert all(s is None for s in r.batcher.scheduler.slots)


def test_http_capacity_reject_ends_stream_cleanly(stack):
    """A request the replica cache can NEVER hold is rejected at
    admission; its stream must end (end chunk, zero tokens) instead of
    hanging the client."""
    engine, params, _ = stack

    async def main():
        router, door = _door(engine, params)        # max_len 16
        await door.start()
        chunks = await _generate(door.port, 0, n_new=64)   # 8+64 > 16
        await door.close()
        return router, chunks

    router, chunks = asyncio.run(main())
    assert len(chunks) == 1
    end = chunks[0]
    assert end["event"] == "end" and not end["done"]
    assert end["n_tokens"] == 0 and end["ttft_s"] is None
    assert router.report().n_rejected == 1


def _streamed(engine, params, obs):
    """Four concurrent clients through a front door built with ``obs``;
    returns the router and each client's chunks."""
    async def main():
        router, door = _door(engine, params, obs=obs)
        await door.start()
        streams = await asyncio.gather(
            *(_generate(door.port, i, n_new=3 + i) for i in range(4)))
        await door.close()
        return router, streams

    return asyncio.run(main())


def test_http_traced_clock_first_then_one_sent_per_streamed_token(stack):
    """With a tracer the trace opens with the wall clock's origin on the
    host's monotonic scale, and the front door records exactly one
    ``sent`` per token chunk it streamed, after its commit, under the
    stream's rid. Delivery events stay out of the lifecycle spans."""
    engine, params, _ = stack
    obs = Observability(tracer=TraceRecorder())
    router, streams = _streamed(engine, params, obs)
    events = obs.tracer.events
    assert events[0] == {"t": 0.0, "event": "clock",
                         "monotonic": router._clock.origin}
    assert sum(e["event"] == "clock" for e in events) == 1
    sent = [e for e in events if e["event"] == "sent"]
    for chunks in streams:
        toks, end = chunks[:-1], chunks[-1]
        mine = [e for e in sent if e["rid"] == end["rid"]]
        assert len(mine) == len(toks) == end["n_tokens"]
        assert [e["committed"] for e in mine] == [c["t"] for c in toks]
        assert all(e["committed"] <= e["t"] for e in mine)
    assert len(sent) == sum(len(c) - 1 for c in streams)
    assert all(e["event"] != "sent"
               for span in obs.tracer.spans().values() for e in span)


def test_http_untraced_records_nothing_and_streams_same_tokens(stack,
                                                               monkeypatch):
    """Without a tracer the front door records nothing (not even a call
    into the tracing helper per chunk) and the clients receive the same
    tokens as a traced run."""
    engine, params, _ = stack
    calls = []
    real = Observability.trace

    def spy(self, event, *a, **kw):
        calls.append(event)
        return real(self, event, *a, **kw)

    monkeypatch.setattr(Observability, "trace", spy)
    router, plain = _streamed(engine, params, None)
    assert router.obs.tracer is None
    assert "sent" not in calls
    traced_obs = Observability(tracer=TraceRecorder())
    _, traced = _streamed(engine, params, traced_obs)
    assert "sent" in calls and len(traced_obs.tracer) > 0

    def tokens(streams):
        return [[c["token"] for c in chunks[:-1]] for chunks in streams]
    assert tokens(plain) == tokens(traced)


def test_http_tokens_handed_to_socket_in_the_round_that_commits_them(stack):
    """Delivery on commit: every token's ``sent`` event comes before the
    next ``round`` event of the replica whose round committed it, so no
    token waits a round in the server; every stream arrives whole and in
    order."""
    engine, params, _ = stack
    N_CLIENTS, N_NEW = 4, 8
    obs = Observability(tracer=TraceRecorder())

    async def main():
        router, door = _door(engine, params, obs=obs, max_len=24)
        await door.start()
        streams = await asyncio.gather(
            *(_generate(door.port, i, n_new=N_NEW)
              for i in range(N_CLIENTS)))
        await door.close()
        return streams

    streams = asyncio.run(main())
    events = obs.tracer.events
    rounds = [(i, e) for i, e in enumerate(events) if e["event"] == "round"]
    sent = [(i, e) for i, e in enumerate(events) if e["event"] == "sent"]
    assert len(sent) == N_CLIENTS * N_NEW
    for i, s in sent:
        # the committing round: the latest of the stream's rounds that
        # began at or before the commit
        j, commit = max(((j, r) for j, r in rounds
                         if s["rid"] in r["rids"]
                         and r["t"] <= s["committed"]),
                        key=lambda jr: jr[1]["t"])
        nxt = [k for k, r in rounds
               if k > j and r["replica"] == commit["replica"]]
        assert not nxt or i < nxt[0], (s, commit)
    for chunks in streams:
        toks, end = chunks[:-1], chunks[-1]
        assert len(toks) == N_NEW and end["event"] == "end"
        assert end["n_tokens"] == N_NEW and end["done"]
        assert [c["prefill"] for c in toks] == [True] + [False] * (N_NEW - 1)
        assert [c["done"] for c in toks] == [False] * (N_NEW - 1) + [True]
        mine = [s for _, s in sent if s["rid"] == end["rid"]]
        assert [s["committed"] for s in mine] == [c["t"] for c in toks]


class _NarrowDoor(HttpFrontDoor):
    """A front door whose sockets push back early: a small kernel send
    buffer and a small transport high-water mark, so a client that stops
    reading fills them within a few hundred tokens."""

    async def _handle(self, reader, writer):
        writer.get_extra_info("socket").setsockopt(
            socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        writer.transport.set_write_buffer_limits(high=1024)
        await super()._handle(reader, writer)


def test_http_paused_client_backpressure_buffers_without_stalling_others(
        stack):
    """A client that stops reading mid-stream (small receive buffer,
    reads paused) makes its handler wait on the socket's drain while the
    rounds go on: another client streams to completion meanwhile, the
    paused client then receives every token in order, and the waits are
    counted in ``repro_http_backpressure_waits_total`` on ``/metrics``."""
    engine, params, _ = stack
    N_SLOW, N_FAST = 300, 6

    async def main():
        router = EventRouter(
            _pool(engine, params, lat=WALL_LAT, n_slots=4,
                  max_len=PROMPT + N_SLOW + 8),
            QueueDepthPolicy(max_replicas=1), clock=WallClock(),
            traffic_name="http")
        door = _NarrowDoor(router, port=0)
        await door.start()
        loop = asyncio.get_running_loop()
        sock = socket.socket()
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1024)
        sock.setblocking(False)
        await loop.sock_connect(sock, ("127.0.0.1", door.port))
        reader, writer = await asyncio.open_connection(sock=sock, limit=256)
        body = json.dumps({"prompt": [3] * PROMPT, "max_new_tokens": N_SLOW})
        writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n{body}").encode())
        await writer.drain()
        assert b"200" in await reader.readline()
        while (await reader.readline()) not in (b"\r\n", b"\n"):
            pass
        first = await _read_chunk(reader)          # mid-stream: now pause
        fast = await asyncio.wait_for(_generate(door.port, 1,
                                                n_new=N_FAST), 60)
        while len(router.completed) < 2:          # the slow stream ends
            await asyncio.sleep(0.01)
        _, metrics = await _get(door.port, "/metrics", bytes.decode)
        rest = []
        while (chunk := await _read_chunk(reader)) is not None:
            rest.append(chunk)
        writer.close()
        await door.close()
        return router, fast, [first] + rest, metrics

    router, fast, slow, metrics = asyncio.run(main())
    assert len(fast) == N_FAST + 1 and fast[-1]["done"]
    toks, end = slow[:-1], slow[-1]
    assert len(toks) == N_SLOW and end["event"] == "end" and end["done"]
    assert end["n_tokens"] == N_SLOW
    assert [c["done"] for c in toks] == [False] * (N_SLOW - 1) + [True]
    assert all(c0["t"] <= c1["t"] for c0, c1 in zip(toks, toks[1:]))
    served = next(q for q in router.completed if q.rid == end["rid"])
    assert [c["token"] for c in toks] == [int(t) for t in served.generated]
    waits = [ln for ln in metrics.splitlines()
             if ln.startswith("repro_http_backpressure_waits_total ")]
    assert len(waits) == 1 and float(waits[0].split()[1]) > 0
    assert router.report().n_cancelled == 0
