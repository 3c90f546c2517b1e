"""Continuous batching: slot-based admission over a SHARED batched cache.

A fixed number of decode slots map onto the rows of ONE batched KV cache
(the vLLM-style scheduling idea at the granularity this framework needs).
The model's per-row ``cache.lengths`` make the batch ragged: every row
decodes at its own position, so each scheduling round issues exactly
**one** ``Engine.decode`` dispatch regardless of how many slots are
active — decode throughput scales with the hardware, not with dispatch
overhead (the same amortization lever the paper pulls by fanning a
monolithic job out over parallel workers).

Three layers:
  * ``SlotScheduler`` — pure bookkeeping (which slot serves which
    request); no arrays, no device state.
  * ``ContinuousBatcher`` (``batched=True``, default) — one
    (n_slots, max_len, …) cache in the engine's planned sharding;
    admission = ``Engine.prefill_into`` writes row *b* (sharding
    preserved, never gathered), eviction = ``Engine.free_row`` zeroes
    row *b*'s length (free rows are masked by ``lengths``), and every
    round is ONE batched decode dispatch. The cache-shape bucket is
    stable, so ``engine.compile_count`` stays flat across admit/evict
    churn (asserted by tests/test_serving_sharded.py).
  * ``batched=False`` — the legacy per-slot path (one batch-1 cache and
    one decode dispatch per active slot per round); kept as the
    benchmark baseline that ``benchmarks/serving_bench.py`` compares
    against.

Used by the serve_cluster example, the serving benchmarks, and the
online router (``repro.router`` — each pool replica wraps one
``ContinuousBatcher(batched=True)`` over the shared engine).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import span
from repro.serving.engine import Engine
from repro.serving.paged import PageAllocator, PagesExhausted
from repro.serving.sampler import sample

# The BENCH_8 time-attribution taxonomy (benchmarks/profiling.py uses
# the same names): where a scheduling round's wall time goes.
BUCKETS = ("prefill", "decode_attention", "sampler", "host_scheduler")
# the host span (``repro:<name>``, repro.obs.trace.span) that covers the
# same stretch as each dispatch bucket
_SPANS = {"prefill": "prefill", "decode_attention": "decode"}


@dataclasses.dataclass
class Request:
    """One generation request. The core fields drive the batcher; the
    timestamp/SLO fields are stamped by the online router
    (``repro.router``) on its virtual clock and stay ``None`` for the
    offline benchmark workloads."""

    rid: int
    prompt: np.ndarray      # (S,) int32
    max_new_tokens: int
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    arrival_t: Optional[float] = None       # entered the arrival queue
    deadline_s: Optional[float] = None      # SLO: finish within this of arrival
    first_token_t: Optional[float] = None   # first streamed token (TTFT)
    finish_t: Optional[float] = None        # last token committed
    n_retries: int = 0
    priority: int = 0       # arrival-queue class: lower dispatches first

    def reset_for_retry(self):
        """Crash re-queue (the paper's retry semantics): in-flight work is
        lost and the request re-runs from scratch. ``first_token_t`` is
        kept — the client already saw that token on the stream."""
        self.generated = []
        self.done = False
        self.n_retries += 1


@dataclasses.dataclass
class SlotScheduler:
    """Tracks which decode slot serves which request.

    Admission protocol (what ``ContinuousBatcher`` drives):
      1. ``submit(req)`` queues a request (FIFO).
      2. ``admit()`` fills every free slot from the queue and returns the
         newly-admitted slot ids — the caller prefills exactly these.
      3. per decode round, ``step_done(slot, token)`` appends one token;
         a request reaching ``max_new_tokens`` completes and frees its
         slot (the caller frees that slot's cache row — eviction).
      4. ``idle`` when the queue is empty and every slot is free.

    The scheduler never touches arrays: cache ownership lives with the
    caller, keyed by slot id.
    """

    n_slots: int

    def __post_init__(self):
        self.slots: List[Optional[Request]] = [None] * self.n_slots
        self.queue: List[Request] = []
        self.completed: List[Request] = []

    def submit(self, req: Request):
        self.queue.append(req)

    def admit(self) -> List[int]:
        """Fill free slots from the queue; returns newly-admitted slot ids."""
        admitted = []
        for i in range(self.n_slots):
            if self.slots[i] is None and self.queue:
                self.slots[i] = self.queue.pop(0)
                admitted.append(i)
        return admitted

    def step_done(self, slot: int, token: int):
        req = self.slots[slot]
        if req is None:
            return
        req.generated.append(int(token))
        if len(req.generated) >= req.max_new_tokens:
            req.done = True
            self.completed.append(req)
            self.slots[slot] = None

    @property
    def active(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]

    @property
    def idle(self) -> bool:
        return not self.queue and all(s is None for s in self.slots)


@dataclasses.dataclass
class ContinuousBatcher:
    """Slot-level continuous batching over a mesh-aware ``Engine``.

    ``batched=True`` (default): slots are the rows of ONE shared decode
    cache, allocated lazily at first admission with capacity ``max_len``
    — or, when unset, the longest prompt then visible (slots + queue)
    plus ``run.cache_pad``. A request whose prompt + max_new_tokens
    exceeds the capacity raises immediately (no silent overflow); pass
    ``max_len`` explicitly when later submissions may be longer.
    Admission prefills into a free row, each round issues exactly one
    ragged batched decode dispatch for ALL slots (free rows masked by
    ``cache.lengths``), and completion zeroes the row's length.

    Sampling: greedy by default (``temperature=0``); ``temperature`` /
    ``top_k`` / ``top_p`` / ``seed`` configure the draw. With
    ``fused_sampling=False`` each round's tokens come from one extra
    HOST sampler dispatch over the (B, V) logits; ``fused_sampling=True``
    (batched modes only) draws them INSIDE the decode dispatch
    (``Engine.decode_sample`` / ``prefill_into_sample`` /
    ``extend_row_sample``) — still one decode dispatch per round, now
    with zero sampler dispatches and no logits HBM round-trip. Both
    modes consume one PRNG key per admission and one per round, so at
    the same ``seed`` they emit identical token streams.

    ``batched=False``: legacy per-slot mode — each slot owns a batch-1
    cache and every active slot costs one decode dispatch per round.

    ``paged=True`` (requires ``batched=True`` and no mesh — it silently
    falls back to the dense shared cache otherwise, the documented
    seq-shard fallback): slots are rows of a block-PAGED cache. A
    ``serving.paged.PageAllocator`` maps each row's logical pages onto a
    shared physical pool, admission is ``assign_row_pages`` +
    ``extend_row`` (ONE dispatch cold or warm — a prompt sharing a
    registered prefix maps its leading pages to the existing physical
    copy and only computes the suffix), each round runs the allocator's
    copy-on-write barrier then the SAME single ragged decode dispatch,
    and completion returns the row's pages to the free list. A request
    the pool can't currently hold is requeued at the front (pages free
    as rows complete); one that can NEVER fit is rejected.

    A request whose prompt + max_new_tokens exceeds the shared cache
    capacity is REJECTED at admission (``rejected`` /
    :meth:`take_rejected`) — the round, and every other slot in it,
    stays alive. (This used to raise out of ``step()``, killing a whole
    router round mid-traffic when one long prompt arrived late.)

    Counters: ``decode_dispatches`` = decode calls (what the batched
    mode collapses to 1/round), ``decode_steps`` = slot-steps of decode
    work (identical between modes for the same workload),
    ``sampler_dispatches`` = host-sampler dispatches (0 under
    ``fused_sampling``), ``rounds`` = scheduling rounds driven.

    Streaming-callback contract: when ``on_token`` is set, every token
    COMMIT calls ``on_token(req, token, prefill)`` — ``prefill=True``
    exactly once per admission (the token the admission prefill
    produced), ``False`` for decode-round tokens — in commit order,
    AFTER the scheduler bookkeeping for that token (``req.done`` is
    accurate). Free rows riding in the decode dispatch never fire it
    (their sampled tokens are discarded). The router's event core
    installs a fresh collector around each round; the batcher never
    calls it for tokens it did not commit, so a caller that discards a
    crashed round's events gets rollback for free.
    """

    engine: Engine
    params: Any
    n_slots: int = 4
    max_len: Optional[int] = None
    batched: bool = True
    paged: bool = False
    page_size: int = 16
    n_pages: Optional[int] = None   # physical pool size; default = worst case
    on_token: Optional[Any] = None  # callback(req, token, prefill) per commit
    fused_sampling: bool = False    # draw tokens inside the decode dispatch
    temperature: float = 0.0        # 0 = greedy (the benchmark default)
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    seed: int = 0                   # PRNG stream for temperature sampling

    def __post_init__(self):
        if self.fused_sampling and not self.batched:
            raise ValueError(
                "fused_sampling requires batched=True — the per-slot "
                "legacy path keeps the host sampler (it exists as the "
                "dispatch-overhead baseline)")
        self.scheduler = SlotScheduler(self.n_slots)
        self.cache: Any = None                # shared batched cache
        self._tokens = np.zeros((self.n_slots, 1), np.int32)
        self.caches: Dict[int, Any] = {}      # per-slot mode: slot -> cache
        self._last_tok: Dict[int, Any] = {}   # per-slot mode: slot -> (1,1)
        self.decode_steps = 0
        self.decode_dispatches = 0
        self.sampler_dispatches = 0   # host-sampler dispatches (0 fused)
        self.rounds = 0
        self.on_token_errors = 0      # subscriber faults contained
        self._bucket_s = {b: 0.0 for b in BUCKETS}
        self._key = None              # lazy PRNGKey(seed) stream
        self.rejected: List[Request] = []
        if self.paged and (self.engine.mesh is not None or not self.batched):
            # paged serving is single-host batched-mode only: mesh
            # layouts (seq_shard in particular needs a contiguous
            # sequence dim to shard) stay on the dense shared cache
            self.paged = False
        self.allocator: Optional[PageAllocator] = None
        self._host_len: Dict[int, int] = {}   # paged: row -> current length

    def submit(self, req: Request):
        self.scheduler.submit(req)

    def submit_many(self, reqs: Sequence[Request]) -> int:
        """Batch admission of a whole shard (the offline batch-DAG
        workload hands a decode task's rows over in one call). Order is
        preserved — rows admit into slots in submission order as
        capacity frees, exactly as if submitted one by one. Returns the
        number queued."""
        for req in reqs:
            self.scheduler.submit(req)
        return len(reqs)

    def take_rejected(self) -> List[Request]:
        """Drain requests rejected at admission (capacity they can never
        fit). The router counts these in its ``rejected`` partition."""
        out, self.rejected = self.rejected, []
        return out

    def _reject(self, slot: int):
        req = self.scheduler.slots[slot]
        self.scheduler.slots[slot] = None
        self.rejected.append(req)

    def take_bucket_s(self) -> Dict[str, float]:
        """Drain the per-round wall-time attribution (BENCH_8 buckets,
        ``BUCKETS`` keys). Live semantics are dispatch-WINDOW wall time
        (no ``block_until_ready`` on the hot path, unlike the offline
        profiler): on an async backend, device time for a dispatch
        surfaces in whichever window forces the host sync — for the
        non-fused decode that's the sampler's ``np.asarray``. Sums to
        measured ``step()`` wall seconds; ``host_scheduler`` is the
        residual."""
        out, self._bucket_s = self._bucket_s, {b: 0.0 for b in BUCKETS}
        return out

    def _fire_on_token(self, req: Request, tok: int, prefill: bool):
        """Subscriber-fault isolation: a raising ``on_token`` callback
        must not corrupt batcher state, kill the round, or double-free
        the row — the commit it observes has already happened. Faults
        are counted (``on_token_errors``) and swallowed."""
        if self.on_token is None or req is None:
            return
        try:
            self.on_token(req, tok, prefill)
        except Exception:
            self.on_token_errors += 1

    # -- sampling seams (identical key schedule in both modes) ----------

    def _next_key(self):
        """Advance the sampling PRNG stream by one key. BOTH sampling
        modes consume exactly one key per admission and one per decode
        round, so ``fused_sampling=True/False`` at the same ``seed``
        produce the same token streams (the parity the fused-sampling
        tests assert)."""
        if self._key is None:
            self._key = jax.random.PRNGKey(self.seed)
        self._key, sub = jax.random.split(self._key)
        return sub

    def _sample_host(self, logits, key) -> np.ndarray:
        """The HOST sampling path: one extra dispatch on the (B, V)
        logits the decode round returned. ``fused_sampling=True`` never
        calls this — its tokens come out of the decode dispatch itself."""
        self.sampler_dispatches += 1
        t0 = time.perf_counter()
        out = np.asarray(sample(logits, key, temperature=self.temperature,
                                top_k=self.top_k, top_p=self.top_p),
                         np.int32)
        self._bucket_s["sampler"] += time.perf_counter() - t0
        return out

    @contextlib.contextmanager
    def _timed(self, bucket: str):
        """One dispatch's stretch of the round: its host span on the
        profiler's clock and its wall-time bucket, opened and closed at
        the same two points so the two cannot disagree. Under fused
        sampling it runs until the token is on the host; without, the
        host sampler that brings it there is the ``sampler`` bucket."""
        t0 = time.perf_counter()
        with span(_SPANS[bucket]):
            try:
                yield
            finally:
                self._bucket_s[bucket] += time.perf_counter() - t0

    def _fused_kw(self) -> dict:
        return dict(temperature=self.temperature, top_k=self.top_k,
                    top_p=self.top_p)

    def step(self) -> List[int]:
        """One scheduling round: admit (prefill) + decode.

        Batched mode decodes every slot in ONE dispatch; per-slot mode
        decodes each active slot separately. Returns the slot ids that
        were newly admitted this round.
        """
        t0 = time.perf_counter()
        attributed0 = sum(self._bucket_s.values())
        with span("round"):
            admitted = self.scheduler.admit()
            if self.paged:
                self._step_paged(admitted)
            elif self.batched:
                self._step_batched(admitted)
            else:
                self._step_per_slot(admitted)
        self.rounds += 1
        attributed = sum(self._bucket_s.values()) - attributed0
        self._bucket_s["host_scheduler"] += max(
            0.0, time.perf_counter() - t0 - attributed)
        return admitted

    # -- batched: one shared cache, one dispatch per round --------------

    def _step_batched(self, admitted: List[int]):
        for slot in admitted:
            req = self.scheduler.slots[slot]
            if self.cache is None:
                if self.max_len is None:
                    # size for every request visible NOW (slots + queue),
                    # with the same cache_pad headroom the per-slot path
                    # gave each request; later, longer prompts raise
                    # loudly below instead of silently overflowing
                    known = [r for r in self.scheduler.slots
                             if r is not None] + self.scheduler.queue
                    self.max_len = max(
                        len(r.prompt) for r in known
                    ) + self.engine.run.cache_pad
                self.cache = self.engine.new_cache(self.n_slots,
                                                   self.max_len)
            if len(req.prompt) + req.max_new_tokens > self.max_len:
                # the cache is already sized — this request can NEVER
                # fit. Reject it and keep the round (and every other
                # slot in it) alive instead of raising out of step().
                self._reject(slot)
                continue
            key = self._next_key()
            with self._timed("prefill"):
                if self.fused_sampling:
                    toks, self.cache = self.engine.prefill_into_sample(
                        self.params, self.cache, slot, req.prompt[None],
                        key, max_len=self.max_len, **self._fused_kw())
                    tok = int(toks[0])
                else:
                    logits, self.cache = self.engine.prefill_into(
                        self.params, self.cache, slot, req.prompt[None],
                        max_len=self.max_len)
            if not self.fused_sampling:
                tok = int(self._sample_host(logits, key)[0])
            self._tokens[slot, 0] = tok
            self._commit_batched(slot, tok, prefill=True)
        if not self.scheduler.active:
            return
        toks = self._decode_all()
        for slot in list(self.scheduler.active):
            self._commit_batched(slot, int(toks[slot]))

    def _decode_all(self) -> np.ndarray:
        """The round's one ragged decode dispatch over every row of the
        shared cache (dense or paged); returns each row's next token on
        the host."""
        key = self._next_key()
        with self._timed("decode_attention"):
            if self.fused_sampling:
                toks, self.cache = self.engine.decode_sample(
                    self.params, self.cache, self._tokens, key,
                    **self._fused_kw())
                toks = np.asarray(toks, np.int32)
            else:
                logits, self.cache = self.engine.decode(
                    self.params, self.cache, self._tokens)
        if not self.fused_sampling:
            toks = self._sample_host(logits, key)
        self.decode_dispatches += 1
        self.decode_steps += len(self.scheduler.active)
        self._tokens[:, 0] = toks
        return toks

    def _commit_batched(self, slot: int, tok: int, prefill: bool = False):
        req = self.scheduler.slots[slot]
        self.scheduler.step_done(slot, tok)
        if self.scheduler.slots[slot] is None:  # completed -> free the row
            self.cache = self.engine.free_row(self.cache, slot)
        self._fire_on_token(req, tok, prefill)

    # -- paged: shared physical pool, prefix sharing, COW, 1 dispatch ---

    def _init_paged(self):
        if self.max_len is None:
            known = [r for r in self.scheduler.slots
                     if r is not None] + self.scheduler.queue
            self.max_len = max(
                len(r.prompt) for r in known) + self.engine.run.cache_pad
        max_pages = -(-self.max_len // self.page_size)
        self.max_len = max_pages * self.page_size  # whole pages
        if self.n_pages is None:
            # worst case — every slot at full capacity — plus null page 0.
            # The HBM win comes from passing a SMALLER pool: rows only
            # consume pages they hold, so a pool sized for the ACTUAL
            # working set serves far more slots at equal KV bytes
            # (benchmarks/serving_bench.py measures exactly this).
            self.n_pages = 1 + self.n_slots * max_pages
        self.allocator = PageAllocator(self.n_pages, self.page_size,
                                       max_pages)
        self.cache = self.engine.new_paged_cache(
            self.n_slots, self.n_pages, self.page_size, max_pages)

    def _step_paged(self, admitted: List[int]):
        for slot in admitted:
            req = self.scheduler.slots[slot]
            if self.cache is None:
                self._init_paged()
            need = len(req.prompt) + req.max_new_tokens
            if need > self.max_len:
                self._reject(slot)   # can never fit a row
                continue
            try:
                plan = self.allocator.admit(slot, req.prompt,
                                            req.max_new_tokens)
            except PagesExhausted:
                if self.allocator.rows and \
                        -(-need // self.page_size) <= self.n_pages - 1:
                    # TRANSIENT: active rows will return pages as they
                    # complete — requeue at the front, keep the round
                    self.scheduler.slots[slot] = None
                    self.scheduler.queue.insert(0, req)
                else:
                    self._reject(slot)  # no active row will ever free
                continue
            key = self._next_key()
            with self._timed("prefill"):
                self.cache = self.engine.assign_row_pages(
                    self.cache, slot, plan.pages, plan.start_len)
                if self.fused_sampling:
                    toks, self.cache = self.engine.extend_row_sample(
                        self.params, self.cache, slot, plan.suffix[None],
                        key, **self._fused_kw())
                    tok = int(toks[0])
                else:
                    logits, self.cache = self.engine.extend_row(
                        self.params, self.cache, slot, plan.suffix[None])
            if not self.fused_sampling:
                tok = int(self._sample_host(logits, key)[0])
            self._host_len[slot] = len(req.prompt)
            self._tokens[slot, 0] = tok
            self._commit_paged(slot, tok, prefill=True)
        if not self.scheduler.active:
            return
        for slot in list(self.scheduler.active):
            # copy-on-write barrier: the page this row writes this round
            # must be exclusively owned (only forked rows ever trip it)
            cow = self.allocator.writable_page(slot, self._host_len[slot])
            if cow is not None:
                src, dst = cow
                self.cache = self.engine.cow_copy_page(self.cache, src,
                                                       dst)
                self.cache = self.engine.assign_row_pages(
                    self.cache, slot, self.allocator.rows[slot],
                    self._host_len[slot])
        toks = self._decode_all()
        for slot in list(self.scheduler.active):
            self._host_len[slot] += 1
            self._commit_paged(slot, int(toks[slot]))

    def _commit_paged(self, slot: int, tok: int, prefill: bool = False):
        req = self.scheduler.slots[slot]
        self.scheduler.step_done(slot, tok)
        if self.scheduler.slots[slot] is None:  # completed -> free pages
            self.allocator.free(slot)
            self._host_len.pop(slot, None)
            self.cache = self.engine.free_row(self.cache, slot)
        self._fire_on_token(req, tok, prefill)

    # -- legacy per-slot: one cache + one dispatch per active slot ------

    def _step_per_slot(self, admitted: List[int]):
        for slot in admitted:
            req = self.scheduler.slots[slot]
            with self._timed("prefill"):
                logits, cache = self.engine.prefill(self.params,
                                                    req.prompt[None])
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
                first = int(tok[0, 0])
            self.caches[slot] = cache
            self._last_tok[slot] = tok
            self._commit_per_slot(slot, first, prefill=True)
        for slot in list(self.scheduler.active):
            with self._timed("decode_attention"):
                logits, cache = self.engine.decode(
                    self.params, self.caches[slot], self._last_tok[slot])
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
                nxt = int(tok[0, 0])
            self.decode_dispatches += 1
            self.decode_steps += 1
            self.caches[slot] = cache
            self._last_tok[slot] = tok
            self._commit_per_slot(slot, nxt)

    def _commit_per_slot(self, slot: int, tok: int, prefill: bool = False):
        req = self.scheduler.slots[slot]
        self.scheduler.step_done(slot, tok)
        if self.scheduler.slots[slot] is None:  # completed -> evict
            self.caches.pop(slot, None)
            self._last_tok.pop(slot, None)
        self._fire_on_token(req, tok, prefill)

    # -- mid-flight cancellation (client disconnect) --------------------

    def cancel(self, req: Request) -> bool:
        """Evict ``req`` by IDENTITY: drop it from the slot queue, or
        free its slot and cache row/pages. Called between rounds (the
        event loop's disconnect path) — the current round, and every
        other slot in it, is untouched. Returns True when found."""
        for i, q in enumerate(self.scheduler.queue):
            if q is req:
                del self.scheduler.queue[i]
                return True
        for slot, q in enumerate(self.scheduler.slots):
            if q is not req:
                continue
            self.scheduler.slots[slot] = None
            if self.paged:
                if self.allocator is not None:
                    self.allocator.free(slot)
                self._host_len.pop(slot, None)
                if self.cache is not None:
                    self.cache = self.engine.free_row(self.cache, slot)
            elif self.batched:
                if self.cache is not None:
                    self.cache = self.engine.free_row(self.cache, slot)
            else:
                self.caches.pop(slot, None)
                self._last_tok.pop(slot, None)
            return True
        return False

    def run(self, max_rounds: int = 10_000) -> List[Request]:
        """Drive rounds until every submitted request completes."""
        rounds = 0
        while not self.scheduler.idle:
            self.step()
            rounds += 1
            if rounds > max_rounds:
                raise RuntimeError("ContinuousBatcher did not drain")
        return self.scheduler.completed
