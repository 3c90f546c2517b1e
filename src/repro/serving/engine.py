"""Inference engine: jit-compiled classify / prefill / decode / generate.

This is the compute payload that the paper's "serverless functions" invoke
(core/worker.py). The engine is mesh-aware end to end: constructed with a
``mesh`` it plans param shardings (``dist.sharding.param_shardings``),
allocates every KV cache in the ``dist.sharding.cache_shardings`` layout
(sequence-sharded over "model" when ``seq_shard=True``), and pins the
prefill→decode handoff with explicit ``jax.jit`` in/out shardings so the
cache NEVER gathers to one device between steps. Without a mesh every
knob degrades to the single-device behavior (how CI and laptop tests run).

The shared-batched-cache admission path (``new_cache`` → ``prefill_into``
→ ``decode`` → ``free_row``) serves continuous batching: one
(n_slots, max_len, …) cache whose per-row ``lengths`` make the decode
batch ragged, so one decode dispatch serves every slot at its own depth.
Row admission and eviction pin the same cache shardings as decode — the
cache layout survives arbitrary admit/evict churn bit-for-bit.

Compilation-cache / shape-bucket contract: every entry point routes
through one executable cache keyed by (kind, input shape bucket).
Repeated worker invocations with the same shapes hit warm executables —
the cold/warm distinction the cost model accounts for — and
``compile_count`` counts bucket misses, which tests and benchmarks use to
assert executable reuse. See serving/README.md for the full contract.
"""
from __future__ import annotations

import dataclasses
import math
import time
from contextlib import nullcontext
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.dist import context as dctx
from repro.dist import sharding as shd
from repro.kernels.decode_attention.fused_sampling import fused_sample
from repro.kernels.decode_attention.quant import KV_DTYPES
from repro.models.common import RunConfig, is_axspec
from repro.models.model_zoo import Model
from repro.obs.trace import span
from repro.serving.sampler import sample


def _shape_key(tree) -> tuple:
    """Hashable shape/dtype bucket for a pytree of arrays or structs.

    The treedef participates in the key: a dense ``Cache`` and a
    ``PagedCache`` (whose static ``page_size`` rides in the treedef's
    aux data) must land in DIFFERENT executable buckets even if their
    leaf shapes happened to coincide.
    """
    return (jax.tree.structure(tree),) + tuple(
        (tuple(l.shape), jnp.dtype(l.dtype).name)
        for l in jax.tree.leaves(tree))


@dataclasses.dataclass
class Engine:
    """Serving engine over one built model.

    Args:
      model: ``models.build(cfg)`` facade.
      run: runtime knobs; ``run.attn_impl`` is forced to ``"seq_shard"``
        when ``seq_shard=True`` under a mesh (the cache layout and the
        attention collective must agree).
      donate_cache: donate the decode cache buffer to each step (the
        in-place KV update; keeps decode HBM traffic at one token).
      mesh: optional ``jax.sharding.Mesh``. When set, all public entry
        points run under ``dist.mesh_context(mesh)`` and accept/produce
        ``NamedSharding``-annotated arrays: params in the planner layout,
        inputs batch-sharded over the data axes, caches in the
        ``cache_shardings`` layout.
      strategy: param-sharding strategy ("tp" | "fsdp" | "fsdp_tp");
        default auto-picks via ``dist.sharding.pick_strategy(kind=
        "infer")``.
      seq_shard: shard the KV-cache SEQUENCE dim over the "model" axis
        (the layout ``dist.collectives.seq_sharded_*`` consumes) instead
        of the default kv-heads layout.
      obs, now: set by :meth:`attach_obs`; ``for_mesh`` carries them to
        each slice engine.
    """

    model: Model
    run: RunConfig = RunConfig()
    donate_cache: bool = True
    mesh: Optional[jax.sharding.Mesh] = None
    strategy: Optional[str] = None
    seq_shard: bool = False
    obs: Optional[Any] = dataclasses.field(default=None, repr=False,
                                           compare=False)
    now: Optional[Callable[[], float]] = dataclasses.field(
        default=None, repr=False, compare=False)

    def __post_init__(self):
        if self.run.kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype={self.run.kv_dtype!r} not in {KV_DTYPES}")
        if self.run.kv_dtype == "int8":
            if self.mesh is not None:
                raise ValueError(
                    "kv_dtype='int8' is single-host only — the sharding "
                    "planner has no layout for the scale leaves (see "
                    "serving/README.md); use kv_dtype='bf16' under a mesh")
            if self.model.cfg.encdec:
                raise ValueError(
                    "encoder-decoder models have no int8 KV layout "
                    "(cross-attn caches stay bf16)")
        if self.mesh is not None:
            if self.seq_shard and self.run.attn_impl != "seq_shard":
                self.run = dataclasses.replace(self.run,
                                               attn_impl="seq_shard")
            if self.strategy is None:
                self.strategy = shd.pick_strategy(
                    self.model.param_specs, self.mesh, kind="infer")
            self.params_sharding = shd.param_shardings(
                self.model.param_specs, self.strategy, self.mesh)
        else:
            self.params_sharding = None
        self._exec: Dict[Any, Any] = {}
        self.compile_count = 0

    # ------------------------------------------------------------------
    # Mesh plumbing
    # ------------------------------------------------------------------

    def _ctx(self):
        """Ambient-mesh context for every jit trace and device_put."""
        return (dctx.mesh_context(self.mesh) if self.mesh is not None
                else nullcontext())

    def _batch_sharding(self, shape) -> Optional[NamedSharding]:
        """Batch-dim-over-data-axes NamedSharding for an output leaf
        (the same rule ``input_shardings`` applies to input leaves)."""
        if self.mesh is None:
            return None
        return shd.input_shardings(
            jax.ShapeDtypeStruct(shape, jnp.float32), self.mesh)

    def for_mesh(self, mesh: Optional[jax.sharding.Mesh]) -> "Engine":
        """A fresh engine over the same model/run knobs bound to ``mesh``
        (its own executable cache and sharding plan). This is how the
        router's mesh-sliced replica pool gives every replica an
        ``Engine(mesh=slice)``: the resolved ``strategy`` carries over,
        and because slices share axis names and shapes, every slice
        engine compiles the same executable buckets — once per slice."""
        return dataclasses.replace(self, mesh=mesh)

    # ------------------------------------------------------------------
    # Observability: what this engine serves, readable from the run
    # ------------------------------------------------------------------

    def attach_obs(self, obs, now: Callable[[], float]) -> None:
        """Report this engine's placement on ``obs`` (a
        ``repro.obs.Observability``), stamping trace events with
        ``now()``: the gauges ``repro_engine_mesh_devices`` and
        ``repro_engine_param_bytes_per_device`` at once, and, with a
        tracer, one ``layout`` event each time :meth:`new_cache` builds a
        serving cache under a mesh."""
        self.obs, self.now = obs, now
        self._report_layout()

    def param_bytes_per_device(self) -> int:
        """Parameter bytes one device holds in the planned layout (all of
        them without a mesh)."""
        specs = jax.tree.leaves(self.model.param_specs, is_leaf=is_axspec)
        if self.mesh is None:
            shapes = [s.shape for s in specs]
        else:
            shapes = [sh.shard_shape(s.shape) for s, sh in
                      zip(specs, jax.tree.leaves(self.params_sharding))]
        return sum(math.prod(shape) * jnp.dtype(s.dtype).itemsize
                   for s, shape in zip(specs, shapes))

    def _report_layout(self, cache_specs=None) -> None:
        obs = self.obs
        if obs is None:
            return
        n_dev = self.mesh.size if self.mesh is not None else 1
        per_dev = self.param_bytes_per_device()
        obs.m_engine_mesh_devices.set(n_dev)
        obs.m_engine_param_bytes.set(per_dev)
        if cache_specs is None or self.mesh is None:
            return
        kv = {tuple(sh.spec) for sh in jax.tree.leaves(
            self.cache_sharding(cache_specs)) if len(sh.spec) >= 4}
        obs.trace("layout", self.now(),
                  mesh=dict(self.mesh.shape), strategy=self.strategy,
                  seq_shard=self.seq_shard, attn_impl=self.run.attn_impl,
                  param_bytes_per_device=per_dev,
                  kv_specs=[list(spec) for spec in sorted(kv, key=str)])

    def shard_params(self, params):
        """Place ``params`` on the device(s): the planner layout under a
        mesh, the default device without one. Host (numpy) leaves are
        uploaded here, once, so no later call re-sends them."""
        if self.mesh is None:
            return jax.device_put(params)
        with self._ctx():
            return jax.device_put(params, self.params_sharding)

    def init_params(self, seed: int):
        """Random params made from ``seed`` directly on the device(s), in
        the layout :meth:`shard_params` gives: under a mesh each device
        materializes only its own shard, so a model larger than one
        device's memory never passes through one device or the host."""
        init = jax.jit(self.model.init, out_shardings=self.params_sharding)
        with self._ctx():
            return init(jax.random.PRNGKey(seed))

    def shard_inputs(self, batch):
        """Batch-shard input leaves over the data axes (dim 0)."""
        batch = jax.tree.map(jnp.asarray, batch)
        if self.mesh is None:
            return batch
        with self._ctx():
            return jax.device_put(
                batch, shd.input_shardings(batch, self.mesh))

    def cache_sharding(self, cache):
        """The planned NamedSharding tree for ``cache`` (None meshless).

        This is the exact tree the decode executable pins as BOTH its
        cache in_sharding and out_sharding — the invariant the sharded
        handoff tests assert across admit/evict cycles.
        """
        if self.mesh is None:
            return None
        return shd.cache_shardings(cache, self.model.cfg, self.mesh,
                                   seq_shard=self.seq_shard)

    # ------------------------------------------------------------------
    # Executable cache
    # ------------------------------------------------------------------

    def _get_exec(self, kind: str, key: tuple, build):
        fn = self._exec.get((kind, key))
        if fn is None:
            fn = build()
            self._exec[(kind, key)] = fn
            self.compile_count += 1
        return fn

    @property
    def warm(self) -> bool:
        """True once at least one executable bucket is compiled — the
        readiness signal ``GET /healthz`` reports: a warm engine serves
        its next request without paying a first-compile stall."""
        return bool(self._exec)

    def _jit_classify(self):
        def _classify(params, tokens):
            logits, _ = self.model.forward(self.run, params,
                                           {"tokens": tokens})
            return logits
        return jax.jit(_classify)

    def _jit_prefill(self, batch_shapes: dict, max_len: int):
        def _prefill(params, batch):
            return self.model.prefill(self.run, params, batch,
                                      max_len=max_len)
        if self.mesh is None:
            return jax.jit(_prefill)
        b = next(iter(batch_shapes.values()))[0]
        cache_sh = self.cache_sharding(self.model.cache_specs(b, max_len))
        logits_sh = self._batch_sharding((b, self.model.cfg.vocab_size))
        return jax.jit(_prefill, out_shardings=(logits_sh, cache_sh))

    def _jit_decode(self, cache):
        donate = (1,) if self.donate_cache else ()

        def _decode(params, cache, token):
            return self.model.decode_step(self.run, params, cache,
                                          {"token": token})
        if self.mesh is None:
            return jax.jit(_decode, donate_argnums=donate)
        cache_sh = self.cache_sharding(cache)
        b = token_b = jax.tree.leaves(cache)[0].shape[1]
        logits_sh = self._batch_sharding((b, self.model.cfg.vocab_size))
        tok_sh = self._batch_sharding((token_b, 1))
        return jax.jit(_decode, donate_argnums=donate,
                       in_shardings=(self.params_sharding, cache_sh,
                                     tok_sh),
                       out_shardings=(logits_sh, cache_sh))

    # ------------------------------------------------------------------
    # Classification (the paper's sentiment inference)
    # ------------------------------------------------------------------

    def classify(self, params, tokens) -> np.ndarray:
        """Batched classification. tokens: (B, S) int32 -> (B,) labels.

        Under a mesh, ``params`` may arrive in any layout (use
        ``shard_params`` once to place them); tokens are batch-sharded
        here and the logits come back batch-sharded. The host span
        ``repro:classify`` covers the call, tokens in to labels on the
        host.
        """
        with span("classify"):
            return np.asarray(jnp.argmax(
                self.classify_logits(params, tokens), axis=-1))

    def classify_logits(self, params, tokens) -> np.ndarray:
        with self._ctx():
            tokens = self.shard_inputs(tokens)
            fn = self._get_exec("classify", _shape_key(tokens),
                                self._jit_classify)
            return np.asarray(fn(params, tokens))

    # ------------------------------------------------------------------
    # Prefill / decode (the sharded handoff)
    # ------------------------------------------------------------------

    def prefill(self, params, tokens, *, max_len: Optional[int] = None
                ) -> Tuple[jax.Array, Any]:
        """tokens (B, S) -> (last-token logits (B, V), populated cache).

        The cache comes back in the ``cache_shardings`` layout (seq-
        sharded over "model" when ``seq_shard=True``) — exactly the
        layout :meth:`decode` pins as its input, so the handoff never
        reshards.
        """
        tokens = jnp.asarray(tokens)
        b, s = tokens.shape
        if max_len is None:
            # `is None`, NOT falsy: an explicit max_len=0 used to silently
            # become s + cache_pad here — callers sizing caches off a
            # conditional expression hit it as corrupted capacity, not an
            # error. Now it raises like any other undersized value.
            max_len = s + self.run.cache_pad
        if max_len <= 0:
            raise ValueError(f"max_len must be positive, got {max_len}")
        if s > max_len:
            raise ValueError(
                f"max_len={max_len} cannot hold the {s}-token prompt")
        with self._ctx():
            batch = self.shard_inputs({"tokens": tokens})
            fn = self._get_exec(
                "prefill", (_shape_key(batch), max_len),
                lambda: self._jit_prefill({"tokens": (b, s)}, max_len))
            return fn(params, batch)

    def decode(self, params, cache, token) -> Tuple[jax.Array, Any]:
        """One decode step; cache sharding is preserved bit-for-bit.

        The executable is pinned with cache in_sharding == out_sharding
        == ``cache_sharding(cache)`` and the buffer is donated, so slot
        admission/eviction cycles around this call can never make SPMD
        gather the cache to one device. The batch is RAGGED: each row
        decodes at its own ``cache.lengths[b]``, so one dispatch serves
        every continuous-batching slot at once.
        """
        with self._ctx():
            token = self.shard_inputs(jnp.asarray(token))
            fn = self._get_exec("decode", _shape_key(cache),
                                lambda: self._jit_decode(cache))
            return fn(params, cache, token)

    # ------------------------------------------------------------------
    # Shared batched cache: allocation / row admission / row free
    # ------------------------------------------------------------------

    def new_cache(self, batch: int, max_len: int,
                  enc_len: Optional[int] = None):
        """Allocate an EMPTY shared batched decode cache (all lengths 0)
        in the planned ``cache_shardings`` layout.

        This is the backing store for batched continuous batching: one
        (batch=n_slots, max_len, …) cache whose rows are admitted into by
        :meth:`prefill_into` and freed by :meth:`free_row`. Under a mesh
        the zeros are created by a jit pinned to the plan, so every
        device allocates only its own shard — the full cache never
        materializes on one device, not even transiently.
        """
        if batch <= 0 or max_len <= 0:
            raise ValueError(
                f"new_cache needs positive batch/max_len, got "
                f"batch={batch} max_len={max_len}")
        specs = self.model.cache_specs(batch, max_len, enc_len,
                                       kv_dtype=self.run.kv_dtype)
        if self.mesh is None:
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                specs)
        self._report_layout(specs)
        with self._ctx():
            fn = self._get_exec(
                "new_cache", _shape_key(specs),
                lambda: jax.jit(
                    lambda: jax.tree.map(
                        lambda s: jnp.zeros(s.shape, s.dtype), specs),
                    out_shardings=self.cache_sharding(specs)))
            return fn()

    def _jit_prefill_into(self, cache, seq_len: int, max_len: int,
                          sample_kw: Optional[dict] = None):
        donate = (1,) if self.donate_cache else ()

        def _prefill_into(params, cache, batch, row, key=None):
            logits, small = self.model.prefill(self.run, params, batch,
                                               max_len=max_len)
            zero = jnp.zeros((), jnp.int32)

            def write(big, sm):
                # batch axis: 0 for the (B,) lengths leaf, 1 elsewhere
                # (leaves lead with a groups/layers dim)
                ax = 0 if big.ndim == 1 else 1
                starts = tuple(row if i == ax else zero
                               for i in range(big.ndim))
                return jax.lax.dynamic_update_slice(
                    big, sm.astype(big.dtype), starts)

            cache = jax.tree.map(write, cache, small)
            if sample_kw is not None:  # fused epilogue: (1,) token out
                return fused_sample(logits, key, **sample_kw), cache
            return logits, cache

        if self.mesh is None:
            return jax.jit(_prefill_into, donate_argnums=donate)
        cache_sh = self.cache_sharding(cache)
        logits_sh = self._batch_sharding((1, self.model.cfg.vocab_size))
        tok_sh = shd.input_shardings(
            jax.ShapeDtypeStruct((1, seq_len), jnp.int32), self.mesh)
        row_sh = NamedSharding(self.mesh, jax.sharding.PartitionSpec())
        if sample_kw is not None:
            key_sh = NamedSharding(self.mesh,
                                   jax.sharding.PartitionSpec())
            tok_out_sh = self._batch_sharding((1,))
            return jax.jit(_prefill_into, donate_argnums=donate,
                           in_shardings=(self.params_sharding, cache_sh,
                                         {"tokens": tok_sh}, row_sh,
                                         key_sh),
                           out_shardings=(tok_out_sh, cache_sh))
        return jax.jit(_prefill_into, donate_argnums=donate,
                       in_shardings=(self.params_sharding, cache_sh,
                                     {"tokens": tok_sh}, row_sh),
                       out_shardings=(logits_sh, cache_sh))

    def prefill_into(self, params, cache, row, tokens, *,
                     max_len: Optional[int] = None
                     ) -> Tuple[jax.Array, Any]:
        """Admit one request into row ``row`` of a shared batched cache.

        tokens: (1, S). Prefills against the shared cache's capacity
        ``max_len`` (pass the value given to :meth:`new_cache`; inferred
        from the cache's KV leaves when omitted) and writes the
        resulting KV/state rows plus ``lengths[row] = S`` into the
        shared cache — under the same pinned in/out ``cache_shardings``,
        so admission never reshards (and never gathers) the cache.
        ``row`` is a traced scalar: one executable per (cache bucket,
        prompt shape), NOT per slot. Returns (last-token logits (1, V),
        updated cache).
        """
        tokens = jnp.asarray(tokens)
        _, s = tokens.shape
        if max_len is None:
            # fall back to the seq dim of any stacked KV leaf
            max_len = next((l.shape[2] for l in jax.tree.leaves(cache)
                            if getattr(l, "ndim", 0) >= 5),
                           s + self.run.cache_pad)
        if max_len <= 0:
            raise ValueError(f"max_len must be positive, got {max_len}")
        if s > max_len:
            raise ValueError(
                f"prompt of {s} tokens exceeds the shared cache's "
                f"capacity of {max_len} — allocate new_cache with a "
                f"larger max_len")
        with self._ctx():
            batch = self.shard_inputs({"tokens": tokens})
            fn = self._get_exec(
                "prefill_into", (_shape_key(cache), _shape_key(batch)),
                lambda: self._jit_prefill_into(cache, s, max_len))
            return fn(params, cache, batch, jnp.asarray(row, jnp.int32))

    def _jit_free_row(self, cache):
        donate = (0,) if self.donate_cache else ()

        def _free(cache, row):
            lengths = jax.lax.dynamic_update_slice(
                cache.lengths, jnp.zeros((1,), cache.lengths.dtype),
                (row,))
            cache = dataclasses.replace(cache, lengths=lengths)
            if hasattr(cache, "page_table"):
                # paged eviction also nulls the row's page table so its
                # inert per-round decode writes land in the reserved
                # null page 0, never in a page another row now owns
                table = jax.lax.dynamic_update_slice(
                    cache.page_table,
                    jnp.zeros((1, cache.page_table.shape[1]),
                              cache.page_table.dtype),
                    (row, jnp.zeros((), jnp.int32)))
                cache = dataclasses.replace(cache, page_table=table)
            return cache

        if self.mesh is None:
            return jax.jit(_free, donate_argnums=donate)
        cache_sh = self.cache_sharding(cache)
        row_sh = NamedSharding(self.mesh, jax.sharding.PartitionSpec())
        return jax.jit(_free, donate_argnums=donate,
                       in_shardings=(cache_sh, row_sh),
                       out_shardings=cache_sh)

    def free_row(self, cache, row):
        """Evict row ``row``: reset its length to 0 (the per-row masks
        make a zero-length row inert; its stale KV is overwritten by the
        next :meth:`prefill_into`). Sharding-preserving and donated."""
        with self._ctx():
            fn = self._get_exec("free_row", _shape_key(cache),
                                lambda: self._jit_free_row(cache))
            return fn(cache, jnp.asarray(row, jnp.int32))

    # ------------------------------------------------------------------
    # Block-paged shared cache (page-table indirection + prefix sharing)
    # ------------------------------------------------------------------
    # Device half of the paged serving path; the host half — which row
    # owns which physical page, refcounts, prefix matching, the COW
    # barrier — is ``serving.paged.PageAllocator``. The lifecycle the
    # batcher drives: new_paged_cache → (admit → assign_row_pages →
    # extend_row) per row → decode (the SAME ragged entry point — the
    # PagedCache bucket routes to the paged kernel) → free_row.
    # Single-host only: under a mesh (and in particular under seq_shard,
    # whose collective needs a contiguous sequence dim to shard) the
    # serving layer stays on the dense shared cache — see
    # serving/README.md.

    def new_paged_cache(self, batch: int, n_pages: int, page_size: int,
                        max_pages: int):
        """Allocate an EMPTY paged cache: zeroed page pools (page 0 =
        reserved null page), all-null page tables, all lengths 0."""
        if self.mesh is not None:
            raise ValueError(
                "paged KV caches are single-host only — use new_cache "
                "under a mesh (see serving/README.md)")
        if min(batch, n_pages, page_size, max_pages) <= 0:
            raise ValueError("paged cache dims must be positive")
        specs = self.model.paged_cache_specs(batch, n_pages, page_size,
                                             max_pages,
                                             kv_dtype=self.run.kv_dtype)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)

    def _jit_assign_row(self):
        donate = (0,) if self.donate_cache else ()

        def _assign(cache, row, table_row, start_len):
            table = jax.lax.dynamic_update_slice(
                cache.page_table, table_row[None],
                (row, jnp.zeros((), jnp.int32)))
            lengths = jax.lax.dynamic_update_slice(
                cache.lengths, start_len[None].astype(cache.lengths.dtype),
                (row,))
            return dataclasses.replace(cache, page_table=table,
                                       lengths=lengths)
        return jax.jit(_assign, donate_argnums=donate)

    def assign_row_pages(self, cache, row, pages, start_len=0):
        """Install ``row``'s logical→physical page map (padded with null
        page 0) and set its length to ``start_len`` — the shared-prefix
        length on warm admission, 0 cold, or the row's current length
        when reinstalling after a copy-on-write repoint. ``row`` and the
        map are traced: one executable per cache bucket, not per slot."""
        max_pages = cache.page_table.shape[1]
        if len(pages) > max_pages:
            raise ValueError(f"{len(pages)} pages exceed the table's "
                             f"max_pages={max_pages}")
        table_row = np.zeros((max_pages,), np.int32)
        table_row[:len(pages)] = pages
        fn = self._get_exec("assign_row", _shape_key(cache),
                            self._jit_assign_row)
        return fn(cache, jnp.asarray(row, jnp.int32),
                  jnp.asarray(table_row),
                  jnp.asarray(start_len, jnp.int32))

    def _jit_extend(self):
        donate = (1,) if self.donate_cache else ()

        def _extend(params, cache, row, tokens):
            return self.model.extend_row(self.run, params, cache, row,
                                         tokens)
        return jax.jit(_extend, donate_argnums=donate)

    def extend_row(self, params, cache, row, tokens
                   ) -> Tuple[jax.Array, Any]:
        """Chunked prefill-with-history of one paged row: ONE dispatch
        whether the row is cold (length 0, tokens = full prompt) or warm
        (length = shared-prefix length, tokens = the divergent suffix —
        the prefix pages are READ, not recomputed). The row's pages must
        already be installed (:meth:`assign_row_pages`). tokens: (1, L);
        returns (last-token logits (1, V), updated cache)."""
        tokens = jnp.asarray(tokens)
        s = tokens.shape[1]
        cap = cache.page_table.shape[1] * cache.page_size
        if s > cap:
            raise ValueError(
                f"{s}-token chunk exceeds the row capacity of {cap} "
                f"({cache.page_table.shape[1]} pages × "
                f"{cache.page_size})")
        fn = self._get_exec("extend_row",
                            (_shape_key(cache), _shape_key(tokens)),
                            self._jit_extend)
        return fn(params, cache, jnp.asarray(row, jnp.int32), tokens)

    def _jit_cow(self):
        donate = (0,) if self.donate_cache else ()

        def _cow(cache, src, dst):
            def copy(pool):
                page = jax.lax.dynamic_index_in_dim(pool, src, 1,
                                                    keepdims=True)
                return jax.lax.dynamic_update_slice_in_dim(pool, page, dst,
                                                           1)
            return dataclasses.replace(
                cache, layers=jax.tree.map(copy, cache.layers))
        return jax.jit(_cow, donate_argnums=donate)

    def cow_copy_page(self, cache, src: int, dst: int):
        """Copy physical page ``src`` → ``dst`` in every layer's K and V
        pool — the device half of the allocator's copy-on-write barrier
        (``PageAllocator.writable_page`` decides WHEN; the caller then
        reinstalls the row's repointed table). Traced scalars: one
        executable per cache bucket."""
        fn = self._get_exec("cow_copy", _shape_key(cache), self._jit_cow)
        return fn(cache, jnp.asarray(src, jnp.int32),
                  jnp.asarray(dst, jnp.int32))

    def _jit_fork(self):
        donate = (0,) if self.donate_cache else ()

        def _fork(cache, src, dst):
            trow = jax.lax.dynamic_index_in_dim(cache.page_table, src, 0,
                                                keepdims=True)
            table = jax.lax.dynamic_update_slice_in_dim(
                cache.page_table, trow, dst, 0)
            lrow = jax.lax.dynamic_index_in_dim(cache.lengths, src, 0,
                                                keepdims=True)
            lengths = jax.lax.dynamic_update_slice_in_dim(
                cache.lengths, lrow, dst, 0)
            return dataclasses.replace(cache, page_table=table,
                                       lengths=lengths)
        return jax.jit(_fork, donate_argnums=donate)

    def fork_row(self, cache, src: int, dst: int):
        """Duplicate row ``src``'s page table and length into ``dst``
        WITHOUT copying any KV (best-of-N decoding: N rows continue from
        one prefill). Pair with ``PageAllocator.fork`` — the shared
        partial tail page is COW'd on the first divergent write."""
        fn = self._get_exec("fork_row", _shape_key(cache), self._jit_fork)
        return fn(cache, jnp.asarray(src, jnp.int32),
                  jnp.asarray(dst, jnp.int32))

    # ------------------------------------------------------------------
    # Fused sampling (token ids out of the decode dispatch — no separate
    # sampler dispatch, no (B, V) logits round-trip through HBM)
    # ------------------------------------------------------------------

    def _fused_kwargs(self, temperature, top_k, top_p):
        # under a mesh the logits arrive vocab-sharded over "model" —
        # force the jnp lowering (the Pallas epilogue wants local vocab)
        return dict(temperature=temperature, top_k=top_k, top_p=top_p,
                    use_kernel=False if self.mesh is not None else None)

    def _jit_decode_sample(self, cache, temperature, top_k, top_p):
        donate = (1,) if self.donate_cache else ()
        kw = self._fused_kwargs(temperature, top_k, top_p)

        def _ds(params, cache, token, key):
            logits, cache = self.model.decode_step(self.run, params, cache,
                                                   {"token": token})
            return fused_sample(logits, key, **kw), cache

        if self.mesh is None:
            return jax.jit(_ds, donate_argnums=donate)
        cache_sh = self.cache_sharding(cache)
        b = jax.tree.leaves(cache)[0].shape[1]
        tok_in_sh = self._batch_sharding((b, 1))
        tok_out_sh = self._batch_sharding((b,))
        key_sh = NamedSharding(self.mesh, jax.sharding.PartitionSpec())
        return jax.jit(_ds, donate_argnums=donate,
                       in_shardings=(self.params_sharding, cache_sh,
                                     tok_in_sh, key_sh),
                       out_shardings=(tok_out_sh, cache_sh))

    def decode_sample(self, params, cache, token, key, *,
                      temperature: float = 0.0,
                      top_k: Optional[int] = None,
                      top_p: Optional[float] = None
                      ) -> Tuple[jax.Array, Any]:
        """One decode step WITH the sampler fused into the executable.

        Same ragged-batch/pinned-sharding/donation contract as
        :meth:`decode`, but returns ((B,) int32 sampled tokens, cache):
        the (B, V) logits never leave the dispatch. At a fixed ``key``
        the tokens equal ``sample(logits, key, ...)`` over
        :meth:`decode`'s logits (the jnp lowering is bit-identical; the
        TPU Pallas epilogue may flip fp near-ties — see
        ``kernels.decode_attention.fused_sampling``). Sampling params are
        static — part of the executable bucket key.
        """
        with self._ctx():
            token = self.shard_inputs(jnp.asarray(token))
            fn = self._get_exec(
                "decode_sample",
                (_shape_key(cache), (temperature, top_k, top_p)),
                lambda: self._jit_decode_sample(cache, temperature, top_k,
                                                top_p))
            return fn(params, cache, token, key)

    def prefill_into_sample(self, params, cache, row, tokens, key, *,
                            temperature: float = 0.0,
                            top_k: Optional[int] = None,
                            top_p: Optional[float] = None,
                            max_len: Optional[int] = None
                            ) -> Tuple[jax.Array, Any]:
        """:meth:`prefill_into` with the first sampled token fused in.

        Returns ((1,) int32 token, updated cache) — the admission's
        last-token logits are sampled inside the same dispatch chain.
        """
        tokens = jnp.asarray(tokens)
        _, s = tokens.shape
        if max_len is None:
            max_len = next((l.shape[2] for l in jax.tree.leaves(cache)
                            if getattr(l, "ndim", 0) >= 5),
                           s + self.run.cache_pad)
        if max_len <= 0:
            raise ValueError(f"max_len must be positive, got {max_len}")
        if s > max_len:
            raise ValueError(
                f"prompt of {s} tokens exceeds the shared cache's "
                f"capacity of {max_len} — allocate new_cache with a "
                f"larger max_len")
        with self._ctx():
            batch = self.shard_inputs({"tokens": tokens})
            fn = self._get_exec(
                "prefill_into_sample",
                (_shape_key(cache), _shape_key(batch),
                 (temperature, top_k, top_p)),
                lambda: self._jit_prefill_into(
                    cache, s, max_len,
                    sample_kw=self._fused_kwargs(temperature, top_k,
                                                 top_p)))
            return fn(params, cache, batch, jnp.asarray(row, jnp.int32),
                      key)

    def _jit_extend_sample(self, temperature, top_k, top_p):
        donate = (1,) if self.donate_cache else ()
        kw = self._fused_kwargs(temperature, top_k, top_p)

        def _es(params, cache, row, tokens, key):
            logits, cache = self.model.extend_row(self.run, params, cache,
                                                  row, tokens)
            return fused_sample(logits, key, **kw), cache
        return jax.jit(_es, donate_argnums=donate)

    def extend_row_sample(self, params, cache, row, tokens, key, *,
                          temperature: float = 0.0,
                          top_k: Optional[int] = None,
                          top_p: Optional[float] = None
                          ) -> Tuple[jax.Array, Any]:
        """:meth:`extend_row` with the first sampled token fused in.
        Returns ((1,) int32 token, updated cache)."""
        tokens = jnp.asarray(tokens)
        s = tokens.shape[1]
        cap = cache.page_table.shape[1] * cache.page_size
        if s > cap:
            raise ValueError(
                f"{s}-token chunk exceeds the row capacity of {cap} "
                f"({cache.page_table.shape[1]} pages × "
                f"{cache.page_size})")
        fn = self._get_exec(
            "extend_row_sample",
            (_shape_key(cache), _shape_key(tokens),
             (temperature, top_k, top_p)),
            lambda: self._jit_extend_sample(temperature, top_k, top_p))
        return fn(params, cache, jnp.asarray(row, jnp.int32), tokens, key)

    # ------------------------------------------------------------------
    # Generation
    # ------------------------------------------------------------------

    def generate(self, params, tokens, *, max_new_tokens: int = 16,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, seed: int = 0,
                 max_len: Optional[int] = None,
                 fused_sampling: bool = False) -> np.ndarray:
        """Greedy/temperature generation. tokens: (B, S) -> (B, S+new).

        Runs the sharded prefill→decode handoff: the cache stays in the
        planner layout for every step; only sampled tokens (B, 1) and the
        final concatenation touch the host. ``fused_sampling=True`` draws
        each round's token inside the decode dispatch
        (:meth:`decode_sample`); the key schedule is IDENTICAL to the
        host-sampler path, so at the same seed both modes emit the same
        stream (up to TPU-kernel fp near-ties).
        """
        tokens = jnp.asarray(tokens)
        with self._ctx():
            logits, cache = self.prefill(params, tokens, max_len=max_len)
            key = jax.random.PRNGKey(seed)
            outs = [tokens]
            if fused_sampling:
                tok = fused_sample(
                    logits, key,
                    **self._fused_kwargs(temperature, top_k, top_p)
                )[:, None]
                for _ in range(max_new_tokens - 1):
                    outs.append(tok)
                    key, sub = jax.random.split(key)
                    toks, cache = self.decode_sample(
                        params, cache, tok, sub, temperature=temperature,
                        top_k=top_k, top_p=top_p)
                    tok = toks[:, None]
            else:
                tok = sample(logits, key, temperature=temperature,
                             top_k=top_k, top_p=top_p)[:, None]
                for _ in range(max_new_tokens - 1):
                    outs.append(tok)
                    key, sub = jax.random.split(key)
                    logits, cache = self.decode(params, cache, tok)
                    tok = sample(logits, sub, temperature=temperature,
                                 top_k=top_k, top_p=top_p)[:, None]
            outs.append(tok)
            return np.asarray(jnp.concatenate(outs, axis=1))


def timed(fn, *args, **kwargs) -> Tuple[Any, float]:
    """Run fn with block_until_ready timing; returns (result, seconds)."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0
