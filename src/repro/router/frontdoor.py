"""Event-driven serving front door: ``EventRouter`` + asyncio HTTP.

The second driver over ``router/events.py``'s ``RouterCore`` (the
synchronous-round ``Router`` is the first). Two modes, one core:

  * ``run_events()`` — VIRTUAL clock. Arrivals become timed events in
    an ``EventQueue`` and the loop alternates deliver-due-events →
    control → replica rounds, recreating the synchronous round barrier
    exactly. Because every mechanic is a shared ``RouterCore`` method,
    this path is bit-identical to ``Router.run()`` at the same seed —
    the parity proof (tests/test_event_router.py) that lets the wall
    path below reuse the same policies, ``FaultInjector`` crashes, and
    metrics with confidence.
  * ``serve()`` — WALL clock, asyncio. Live callers ``submit()``
    requests (no traffic generator) with a sink that the round which
    commits a token calls with it, then once with the stream's end;
    TTFT/TPOT come from REAL timestamps at first-token/per-token
    events. Between rounds the loop yields one turn to the event loop
    so the HTTP handlers accept connections and read requests; when
    idle it sleeps on a wake event (new submission) or the next
    cold-start deadline.

``HttpFrontDoor`` is the thin serving layer on top: a stdlib-only
HTTP/1.1 server (``asyncio.start_server`` — no extra dependencies)
streaming NDJSON token events over chunked transfer encoding. Each
token is written into the client's transport in the round that commits
it (``writer.write`` is synchronous and buffered), with no task or
loop turn between commit and socket. A transport whose buffer is above
its high-water mark keeps that stream's later tokens in order in a
per-request backlog, and the handler awaits ``writer.drain()`` before
writing them (``repro_http_backpressure_waits_total``); the rounds and
every other stream go on meanwhile.

  * ``POST /v1/generate``   body ``{"prompt": [ints], "max_new_tokens":
    n, "priority": p, "deadline_s": s}`` → one chunk per token
    ``{"token", "t", "prefill", "done"}`` + a final
    ``{"event": "end", ...}`` stats chunk.
  * ``GET /healthz`` — liveness + READINESS (false until a serving
    replica exists and its engine is warm — see
    ``EventRouter.readiness``).
  * ``GET /metrics`` — Prometheus text exposition rendered from the
    ``repro.obs`` registry (metric catalog: docs/OBSERVABILITY.md).
  * ``GET /metrics.json`` — the legacy JSON counter blob, now served
    O(1) from live state + registry histograms (``live_stats``).

With a tracer attached (``Observability(tracer=...)``) the front door
records one ``sent`` delivery event per token chunk at the write that
hands it to the client's socket, in the commit round unless the
stream was backed up (docs/OBSERVABILITY.md), and the serve loop's
turn given to the handlers is the host span ``repro:frontdoor``.

A mid-flight client disconnect cancels its request —
``EventRouter.cancel`` frees the slot's cache row via
``ContinuousBatcher.cancel`` between rounds, so the round (and every
other client in it) survives; the freed row is simply re-admitted
from the queue next round. Cancels are counted (``n_cancelled``), not
billed as failures.

Launch: ``python -m repro.launch.serve --http`` (see launch/serve.py).
"""
from __future__ import annotations

import asyncio
import json
from collections import deque
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from repro.core.cost_model import AWSPriceBook, TPUPriceBook
from repro.obs import Observability, span
from repro.router.events import (ARRIVAL, EventQueue, RouterConfig,
                                 RouterCore, VirtualClock)
from repro.router.metrics import RouterReport
from repro.router.policy import AutoscalePolicy
from repro.router.pool import ReplicaPool
from repro.router.queue import QueueConfig
from repro.serving.batching import Request


# A live request's delivery: called with the request and each committed
# token's item, then once with its ``{"event": "end"}`` item
# (``EventRouter.submit``).
Sink = Callable[[Request, Dict[str, Any]], None]


class EventRouter(RouterCore):
    """Event-driven router: virtual event-queue trace driver for parity
    tests and benchmarks, asyncio wall-clock loop for live serving."""

    def __init__(self, pool: ReplicaPool, policy: AutoscalePolicy,
                 traffic=(), queue_cfg: QueueConfig = QueueConfig(),
                 cfg: RouterConfig = RouterConfig(),
                 aws: AWSPriceBook = AWSPriceBook(),
                 tpu: TPUPriceBook = TPUPriceBook(),
                 traffic_name: str = "",
                 clock: Optional[Any] = None,
                 obs: Optional[Observability] = None):
        super().__init__(pool, policy, traffic, queue_cfg, cfg, aws, tpu,
                         traffic_name, clock=clock or VirtualClock(),
                         obs=obs)
        self._intake: deque = deque()        # live submissions, pre-queue
        # id(req) -> (req, sink): where each live request's items go
        self._streams: Dict[int, Tuple[Request, Sink]] = {}
        self._rid_seq = len(traffic)
        self._wake: Optional[asyncio.Event] = None
        self._stopping = False
        self._n_exp_seen = 0
        self._n_rej_seen = 0

    # -- virtual trace mode (the parity/bench harness) -------------------

    def run_events(self) -> RouterReport:
        """Drive the pre-generated trace through the event loop on the
        virtual clock; returns the same fully-accounted report as
        ``Router.run`` — identically, at the same seed."""
        eq = EventQueue()
        while self._pending:
            req = self._pending.popleft()
            eq.push(req.arrival_t, ARRIVAL, req)
        rounds = 0
        while True:
            rounds += 1
            if rounds > self.cfg.max_rounds:
                raise RuntimeError(
                    f"event router did not drain in "
                    f"{self.cfg.max_rounds} rounds")
            # deliver every event due at the current clock
            while eq and eq.peek_t() <= self.clock + 1e-12:
                _, kind, payload = eq.pop()
                if kind == ARRIVAL:
                    self._admit_arrival(payload)
            self._control()
            durations = self._step_all()
            if durations:
                self._clock.advance_to(self.clock + max(durations))
                self.pool.retire_drained(self.clock)
                continue
            if not eq and self._drained():
                break
            self._idle_advance(eq.peek_t())
        self.pool.retire_all(self.clock)
        return self._report()

    # -- live wall-clock mode --------------------------------------------

    def submit(self, prompt, max_new_tokens: int, sink: Sink, *,
               priority: int = 0, deadline_s: Optional[float] = None
               ) -> Request:
        """Live intake: returns the request. ``sink`` is called with it
        and one ``{"token", "t", "prefill", "done"}`` item per token,
        inside the round that commits the token, then once with the
        ``{"event": "end"}`` item (completion, cancellation, expiry, or
        rejection)."""
        req = Request(rid=self._rid_seq,
                      prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=int(max_new_tokens),
                      arrival_t=self.clock, deadline_s=deadline_s,
                      priority=int(priority))
        self._rid_seq += 1
        self._streams[id(req)] = (req, sink)
        self._intake.append(req)
        # fold live requests into the avg-token estimator the trace
        # modes precompute from the full trace
        self._req_tok_sum += (req.max_new_tokens
                              + len(req.prompt) * self._prefill_factor)
        self._req_count += 1
        if self._wake is not None:
            self._wake.set()
        return req

    def cancel(self, req: Request) -> bool:
        """Client went away: remove ``req`` wherever it is — intake,
        arrival queue, or a replica slot (freeing its cache row without
        touching the round). Returns True when found."""
        n_before = len(self._intake)
        self._intake = deque(q for q in self._intake if q is not req)
        found = len(self._intake) != n_before
        found = self.queue.cancel(req) or found
        if not found:
            for r in self.pool.live():
                if r.batcher.cancel(req):
                    found = True
                    break
        if found:
            self.n_cancelled += 1
            self._log("cancel", rid=req.rid)
            if self.obs is not None:
                self.obs.m_requests.inc(outcome="cancelled")
                self.obs.trace("cancel", self.clock, rid=req.rid)
            self._close_stream(req)
            if self._wake is not None:
                self._wake.set()
        return found

    def request_stop(self) -> None:
        """Ask ``serve`` to exit once intake + queue + slots drain."""
        self._stopping = True
        if self._wake is not None:
            self._wake.set()

    async def serve(self) -> None:
        """The wall-clock event loop: admit live intake, run control +
        replica rounds while there is work, sleep on the wake event
        (next submission) or the next cold start otherwise. Exits after
        ``request_stop()`` once fully drained."""
        if self._clock.virtual:
            raise RuntimeError(
                "serve() is the wall-clock path — construct the "
                "EventRouter with clock=WallClock() (run_events() "
                "drives virtual-clock traces)")
        self._wake = asyncio.Event()
        try:
            while True:
                while self._intake:
                    self._admit_arrival(self._intake.popleft())
                self._control()
                self._close_terminal_streams()
                durations = self._step_all()
                if durations:
                    self.pool.retire_drained(self.clock)
                    # the round wrote its tokens to the sockets; give
                    # the handlers one turn to accept and read requests
                    with span("frontdoor"):
                        await asyncio.sleep(0)
                    continue
                if self._stopping and not self._intake and self._drained():
                    break
                waits = [max(r.ready_t - self.clock, 0.0)
                         for r in self.pool.live()
                         if r.state == "starting"]
                timeout = min(waits) + 1e-3 if waits \
                    else self.cfg.idle_step_s
                try:
                    await asyncio.wait_for(self._wake.wait(), timeout)
                except asyncio.TimeoutError:
                    pass
                self._wake.clear()
        finally:
            self.pool.retire_all(self.clock)
            self._close_terminal_streams()
            for req, _ in list(self._streams.values()):
                self._close_stream(req)

    def report(self) -> RouterReport:
        """Accounting so far (wall mode: call after ``serve`` returns
        for final numbers; mid-flight snapshots are fine too)."""
        return self._report()

    def live_stats(self) -> Dict[str, Any]:
        """The legacy JSON scrape shape (``GET /metrics.json``), served
        in O(1) from live counters and registry histograms — NOT from
        ``_report()``, which walks every completed request and runs
        exact percentile math per call (the hot-path bug this replaces).
        The p50s are the registry's bucket-boundary estimates; exact
        percentiles still come from ``report()`` at end of run."""
        obs = self.obs if self.obs is not None else self.attach_obs(
            Observability())
        return {
            "clock_s": round(self.clock, 4),
            "queue_depth": self.queue.depth,
            "n_replicas": len(self.pool.live()),
            "n_completed": len(self.completed),
            "n_cancelled": self.n_cancelled,
            "n_rejected": len(self.queue.rejected),
            "n_expired": len(self.queue.expired),
            "tokens_out": self.pool.tokens_out(),
            "ttft_p50_s": round(obs.m_ttft.quantile(0.5), 4),
            "tpot_p50_s": round(obs.m_tpot.quantile(0.5), 4),
            "cost_usd": round(self._cost_so_far(), 8),
        }

    def readiness(self) -> Dict[str, Any]:
        """``GET /healthz`` body: liveness (``ok``) plus READINESS —
        false through the cold-start window, true once the pool has a
        replica in a serving state AND that replica's engine has at
        least one executable bucket compiled (``Engine.warm``): the
        next request is served without a spawn or first-compile stall."""
        serving = [r for r in self.pool.live()
                   if r.state in ("ready", "draining")]
        warm = any(getattr(r.batcher.engine, "warm", False)
                   for r in serving)
        return {"ok": True, "ready": warm,
                "n_replicas": len(self.pool.live()),
                "n_ready": len(serving)}

    # -- streaming plumbing ----------------------------------------------

    def _emit_round(self, timed) -> None:
        if not self._streams:
            return
        last = {}
        for i, (req, _tok, _t, _prefill) in enumerate(timed):
            last[id(req)] = i
        for i, (req, tok, t, prefill) in enumerate(timed):
            entry = self._streams.get(id(req))
            if entry is None:
                continue
            done = req.done and last[id(req)] == i
            entry[1](req, {"token": tok, "t": t, "prefill": prefill,
                           "done": done})
            if done:
                self._close_stream(req)

    def _close_stream(self, req: Request) -> None:
        entry = self._streams.pop(id(req), None)
        if entry is not None:
            entry[1](req, {
                "event": "end", "rid": req.rid,
                "n_tokens": len(req.generated), "done": req.done,
                "ttft_s": (None if req.first_token_t is None
                           or req.arrival_t is None
                           else req.first_token_t - req.arrival_t),
                "n_retries": req.n_retries,
            })

    def _close_terminal_streams(self) -> None:
        """Requests that will never produce tokens (expired in queue,
        rejected at admission/capacity) must still end their streams."""
        for q in self.queue.expired[self._n_exp_seen:]:
            self._close_stream(q)
        self._n_exp_seen = len(self.queue.expired)
        for q in self.queue.rejected[self._n_rej_seen:]:
            self._close_stream(q)
        self._n_rej_seen = len(self.queue.rejected)


class HttpFrontDoor:
    """Stdlib-asyncio HTTP/1.1 server over an ``EventRouter`` (wall
    clock). Streams NDJSON token chunks; see the module docstring for
    the routes. ``port=0`` binds an ephemeral port (tests)."""

    def __init__(self, router: EventRouter, host: str = "127.0.0.1",
                 port: int = 0):
        self.router = router
        # the front door always serves Prometheus text, so a router
        # built without observability gets a metrics-only one here
        if router.obs is None:
            router.attach_obs(Observability())
        self.obs = router.obs
        self.host = host
        self.port = port
        self._server: Optional[asyncio.AbstractServer] = None
        self._serve_task: Optional[asyncio.Task] = None

    async def start(self) -> None:
        self._server = await asyncio.start_server(self._handle, self.host,
                                                  self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        self._serve_task = asyncio.create_task(self.router.serve())

    async def close(self) -> None:
        """Stop accepting, drain the router, join its loop."""
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
        self.router.request_stop()
        if self._serve_task is not None:
            await self._serve_task

    # -- request handling ------------------------------------------------

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        self.obs.m_http_inflight.inc()
        try:
            line = await reader.readline()
            if not line:
                return
            parts = line.decode("latin-1").split(" ")
            if len(parts) < 2:
                return
            method, path = parts[0], parts[1]
            headers = {}
            while True:
                h = await reader.readline()
                if h in (b"\r\n", b"\n", b""):
                    break
                k, _, v = h.decode("latin-1").partition(":")
                headers[k.strip().lower()] = v.strip()
            if method == "GET" and path == "/healthz":
                await self._json(writer, 200, self.router.readiness())
            elif method == "GET" and path == "/metrics":
                await self._text(writer, 200,
                                 self.obs.registry.render())
            elif method == "GET" and path == "/metrics.json":
                await self._json(writer, 200, self.router.live_stats())
            elif method == "POST" and path == "/v1/generate":
                await self._generate(reader, writer, headers)
            else:
                await self._json(writer, 404, {"error": "not found"})
        except (ConnectionResetError, BrokenPipeError,
                asyncio.IncompleteReadError):
            pass
        finally:
            self.obs.m_http_inflight.dec()
            try:
                writer.close()
                await writer.wait_closed()
            except Exception:
                pass

    async def _generate(self, reader: asyncio.StreamReader,
                        writer: asyncio.StreamWriter,
                        headers: Dict[str, str]) -> None:
        n = int(headers.get("content-length", "0"))
        body = await reader.readexactly(n) if n else b"{}"
        try:
            spec = json.loads(body.decode() or "{}")
        except (ValueError, UnicodeDecodeError):
            await self._json(writer, 400, {"error": "bad json"})
            return
        writer.write(b"HTTP/1.1 200 OK\r\n"
                     b"Content-Type: application/x-ndjson\r\n"
                     b"Transfer-Encoding: chunked\r\n"
                     b"Connection: close\r\n\r\n")
        sink = _Delivery(self, writer)
        req = self.router.submit(
            spec.get("prompt") or [], int(spec.get("max_new_tokens", 16)),
            sink, priority=int(spec.get("priority", 0)),
            deadline_s=spec.get("deadline_s"))
        # the request body is fully read, so any further read resolving
        # means the client went away (EOF / reset) -> cancel mid-flight
        watchdog = asyncio.ensure_future(reader.read(1))
        try:
            while not sink.finished:
                await asyncio.wait({sink.cue, watchdog},
                                   return_when=asyncio.FIRST_COMPLETED)
                if not sink.cue.done():    # client disconnected
                    self._disconnected(writer, req)
                    return
                if sink.backlog:
                    # delivery outran the socket: wait until it takes
                    # more, then write what waited
                    self.obs.m_http_backpressure_waits.inc()
                    await writer.drain()
                    sink.flush()
        except (ConnectionResetError, BrokenPipeError):
            self._disconnected(writer, req)
        finally:
            watchdog.cancel()

    def _disconnected(self, writer: asyncio.StreamWriter,
                      req: Request) -> None:
        writer.close()        # so the cancelled stream writes nothing more
        self.obs.m_http_disconnects.inc()
        self.router.cancel(req)

    # -- wire helpers ----------------------------------------------------

    @staticmethod
    async def _json(writer: asyncio.StreamWriter, status: int,
                    obj: Any) -> None:
        body = (json.dumps(obj) + "\n").encode()
        reason = {200: "OK", 400: "Bad Request", 404: "Not Found"}.get(
            status, "")
        writer.write(f"HTTP/1.1 {status} {reason}\r\n"
                     f"Content-Type: application/json\r\n"
                     f"Content-Length: {len(body)}\r\n"
                     f"Connection: close\r\n\r\n".encode() + body)
        await writer.drain()

    @staticmethod
    async def _text(writer: asyncio.StreamWriter, status: int,
                    text: str) -> None:
        """Prometheus text exposition (``GET /metrics``)."""
        body = text.encode()
        writer.write(
            f"HTTP/1.1 {status} OK\r\n"
            f"Content-Type: text/plain; version=0.0.4; charset=utf-8\r\n"
            f"Content-Length: {len(body)}\r\n"
            f"Connection: close\r\n\r\n".encode() + body)
        await writer.drain()


class _Delivery:
    """One HTTP stream's sink (``EventRouter.submit``): writes each item
    as an NDJSON chunk straight into the client's transport, inside the
    round that committed it, and the end item with the chunked-body
    terminator. While the transport's buffer is above its high-water
    mark, items wait in ``backlog`` and ``cue`` wakes the handler to
    drain the socket; ``cue`` also resolves once the end is written."""

    def __init__(self, door: HttpFrontDoor, writer: asyncio.StreamWriter):
        self.door = door
        self.transport = writer.transport
        self.tracer = door.obs.tracer
        self.backlog: deque = deque()
        self.cue: asyncio.Future = asyncio.get_running_loop().create_future()
        self.finished = False     # the end chunk is written

    def __call__(self, req: Request, item: Dict[str, Any]) -> None:
        if self.backlog or self._above_mark():
            self.backlog.append((req, item))
            self._rouse()
        else:
            self._write(req, item)

    def flush(self) -> None:
        """After a drain: write what waited, until the transport's
        buffer is above its mark again."""
        self.cue = asyncio.get_running_loop().create_future()
        while self.backlog and not self._above_mark():
            self._write(*self.backlog.popleft())
        if self.backlog:
            self._rouse()

    def _above_mark(self) -> bool:
        t = self.transport
        return t.get_write_buffer_size() > t.get_write_buffer_limits()[1]

    def _write(self, req: Request, item: Dict[str, Any]) -> None:
        end = "event" in item
        if not self.transport.is_closing():
            data = (json.dumps(item) + "\n").encode()
            self.transport.write(f"{len(data):x}\r\n".encode() + data
                                 + (b"\r\n0\r\n\r\n" if end else b"\r\n"))
            if self.tracer is not None and not end:
                # delivery, on the router's clock: the token is handed
                # to the socket now; it was committed at item["t"]
                self.door.obs.trace("sent", self.door.router.clock,
                                    rid=req.rid, committed=item["t"])
        if end:
            self.finished = True
            self._rouse()

    def _rouse(self) -> None:
        if not self.cue.done():
            self.cue.set_result(None)
