"""Unified decoder LM over heterogeneous block patterns.

A model is ``n_groups`` repetitions of a ``pattern`` (tuple of LayerSpec).
Per pattern position, parameters are stacked along a leading "layers" axis of
size n_groups, and the forward pass is a ``lax.scan`` over groups — keeping
HLO size O(period), which is what makes 96-layer × 512-device dry-run
compiles fast.

Entry points:
  * forward      — full-sequence logits (training / eval)
  * prefill      — full-sequence pass that also builds the decode cache
  * decode_step  — one token in, one token out, cache updated in place
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.dist import context as dctx
from repro.models import attention as attn_lib
from repro.models import mlp as mlp_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.common import (AxSpec, LayerSpec, ModelConfig, RunConfig,
                                 abstract_params, apply_norm, norm_spec,
                                 softcap, tree_map_spec)

# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


def _stack(tree, g: int):
    """Prepend a stacked "layers" dim of size g to every AxSpec leaf."""
    return tree_map_spec(
        lambda s: AxSpec((g,) + s.shape, ("layers",) + s.axes, s.init,
                         s.dtype, s.scale), tree)


def _position_specs(cfg: ModelConfig, spec: LayerSpec):
    p: dict = {"norm1": norm_spec(cfg)}
    if spec.mixer.startswith("attn"):
        p["attn"] = attn_lib.attn_specs(cfg)
    elif spec.mixer == "ssm":
        p["ssm"] = ssm_lib.ssm_specs(cfg, cfg.ssm)
    else:
        raise ValueError(spec.mixer)
    if cfg.sandwich_norms:
        p["post_norm1"] = norm_spec(cfg)
    if spec.mlp == "dense":
        p["norm2"] = norm_spec(cfg)
        p["mlp"] = mlp_lib.mlp_specs(cfg)
    elif spec.mlp == "moe":
        p["norm2"] = norm_spec(cfg)
        p["moe"] = moe_lib.moe_specs(cfg, cfg.moe)
    elif spec.mlp != "none":
        raise ValueError(spec.mlp)
    if cfg.sandwich_norms and spec.mlp != "none":
        p["post_norm2"] = norm_spec(cfg)
    return p


def lm_specs(cfg: ModelConfig):
    g = cfg.n_groups
    specs = {
        "embed": AxSpec((cfg.vocab_size, cfg.d_model), ("vocab", "d_model"),
                        "embed"),
        "blocks": tuple(_stack(_position_specs(cfg, s), g)
                        for s in cfg.pattern),
        "final_norm": norm_spec(cfg),
    }
    if cfg.num_labels:
        specs["cls_head"] = AxSpec((cfg.d_model, cfg.num_labels),
                                   ("d_model", None))
    elif not cfg.tie_embeddings:
        specs["lm_head"] = AxSpec((cfg.d_model, cfg.vocab_size),
                                  ("d_model", "vocab"))
    if cfg.pos == "learned":
        specs["pos_embed"] = AxSpec((cfg.max_position, cfg.d_model),
                                    ("vocab", "d_model"), "embed")
    return specs


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


def _apply_block_position(cfg: ModelConfig, run: RunConfig, spec: LayerSpec,
                          p, x, positions, aux):
    """One pattern position (mixer + mlp with residuals); full-seq path."""
    h = apply_norm(cfg, p["norm1"], x)
    if spec.mixer.startswith("attn"):
        h = attn_lib.attn_forward(
            cfg, p["attn"], h, mixer=spec.mixer, positions=positions,
            impl=run.attn_impl,
            mask_kind="bidir" if cfg.bidirectional else "causal")
    else:
        h = ssm_lib.ssm_forward(cfg, cfg.ssm, p["ssm"], h)
    if cfg.sandwich_norms:
        h = apply_norm(cfg, p["post_norm1"], h)
    x = x + h
    if spec.mlp != "none":
        h = apply_norm(cfg, p["norm2"], x)
        if spec.mlp == "moe":
            h, a = moe_lib.moe_apply(cfg, cfg.moe, p["moe"], h,
                                     impl=run.moe_impl)
            aux = aux + a["lb_loss"]
        else:
            h = mlp_lib.mlp_apply(cfg, p["mlp"], h)
        if cfg.sandwich_norms:
            h = apply_norm(cfg, p["post_norm2"], h)
        x = x + h
    return x, aux


def _residual_constrain(run: RunConfig, x):
    """Residual-stream layout: Megatron-SP shards the sequence dim over
    "model" (halves the per-block collective bytes: the MLP/attn output
    all-reduce decomposes into reduce-scatter + all-gather), otherwise
    batch-only sharding."""
    if run.seq_parallel and x.ndim == 3 and x.shape[1] > 1:
        return dctx.constrain(x, "model", None)
    return dctx.constrain(x, None, None)


def _group_body(cfg: ModelConfig, run: RunConfig, x, aux, group_params,
                positions):
    for spec, p in zip(cfg.pattern, group_params):
        x, aux = _apply_block_position(cfg, run, spec, p, x, positions, aux)
        x = _residual_constrain(run, x)
    return x, aux


def _maybe_remat(fn, run: RunConfig):
    if run.remat == "none":
        return fn
    if run.remat == "full":
        return jax.checkpoint(fn)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def _embed_in(cfg: ModelConfig, params, tokens=None, embeddings=None,
              positions=None):
    if embeddings is not None:
        x = embeddings.astype(jnp.bfloat16)
    else:
        # activations take the embedding's dtype: bf16 when served, fp32
        # for a float32 reference run over upcast params
        x = params["embed"][tokens]
    if cfg.emb_scale:
        x = x * jnp.sqrt(float(cfg.d_model)).astype(x.dtype)
    if cfg.pos == "learned":
        x = x + jnp.take(params["pos_embed"], positions, axis=0
                         ).astype(x.dtype)
    return dctx.constrain(x, None, None)


def _lm_head(cfg: ModelConfig, params, x):
    if cfg.num_labels:
        return jnp.einsum("...d,dc->...c", x,
                          params["cls_head"].astype(x.dtype)
                          ).astype(jnp.float32)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x,
                            params["embed"].astype(x.dtype))
    else:
        logits = jnp.einsum("...d,dv->...v", x,
                            params["lm_head"].astype(x.dtype))
    logits = dctx.constrain(logits, *([None] * (logits.ndim - 2)), "model")
    return softcap(logits.astype(jnp.float32), cfg.final_softcap)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def forward(cfg: ModelConfig, run: RunConfig, params, *, tokens=None,
            embeddings=None):
    """Full-sequence logits. Returns (logits_fp32, aux_loss)."""
    seq = (tokens if tokens is not None else embeddings).shape[1]
    positions = jnp.arange(seq)[None, :]
    x = _embed_in(cfg, params, tokens, embeddings, positions)

    body = _maybe_remat(
        lambda xa, gp: _group_body(cfg, run, xa[0], xa[1], gp, positions), run)

    if run.scan_layers:
        def scan_body(carry, gp):
            return body(carry, gp), None
        (x, aux), _ = jax.lax.scan(
            scan_body, (x, jnp.zeros((), jnp.float32)), params["blocks"])
    else:
        aux = jnp.zeros((), jnp.float32)
        g = cfg.n_groups
        for gi in range(g):
            gp = jax.tree.map(lambda t: t[gi], params["blocks"])
            x, aux = body((x, aux), gp)

    x = apply_norm(cfg, params["final_norm"], x)
    if cfg.num_labels:  # encoder classifier: pool at [CLS] position 0
        return _lm_head(cfg, params, x[:, 0]), aux / max(cfg.n_layers, 1)
    return _lm_head(cfg, params, x), aux / max(cfg.n_layers, 1)


# ---------------------------------------------------------------------------
# Decode cache
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Cache:
    """Decode cache: per-pattern-position stacked layer caches + per-row
    lengths.

    ``lengths`` is (B,) — each batch row tracks its own number of valid
    tokens, so one shared batched cache can hold requests at different
    decode depths (ragged continuous batching). A free/evicted row is a
    row whose length the serving layer reset to 0; the per-row masks make
    it inert until the next admission overwrites the row.
    """

    layers: tuple  # tuple over pattern positions; leaves lead with (G, ...)
    lengths: Any   # (B,) int32 — per-row number of valid tokens

    def tree_flatten(self):
        return (self.layers, self.lengths), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


def cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                kv_dtype: str = "bf16"):
    """Abstract cache tree (ShapeDtypeStruct leaves) for the dry-run.

    ``kv_dtype="int8"`` stores KV leaves as int8 and adds per-token fp32
    ``k_scale``/``v_scale`` leaves of shape (G, B, max_len, KV, 1) — the
    dense layout of ``kernels.decode_attention.quant`` (attention layers
    only; SSM state is untouched).
    """
    g = cfg.n_groups
    layers = []
    for spec in cfg.pattern:
        if spec.mixer.startswith("attn"):
            shape = (g, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
            if kv_dtype == "int8":
                kv = jax.ShapeDtypeStruct(shape, jnp.int8)
                sc = jax.ShapeDtypeStruct(shape[:-1] + (1,), jnp.float32)
                layers.append({"k": kv, "v": kv,
                               "k_scale": sc, "v_scale": sc})
            else:
                kv = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
                layers.append({"k": kv, "v": kv})
        else:
            one = ssm_lib.ssm_cache_specs(cfg, cfg.ssm, batch)
            layers.append(jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((g,) + s.shape, s.dtype), one))
    return Cache(layers=tuple(layers),
                 lengths=jax.ShapeDtypeStruct((batch,), jnp.int32))


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               kv_dtype: str = "bf16"):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_specs(cfg, batch, max_len, kv_dtype))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedCache:
    """Block-paged decode cache: shared physical page pools + per-row page
    tables.

    KV leaves are ``(G, n_pages, page_size, KV, hd)`` — a POOL of physical
    pages with no batch dim; ``page_table`` (B, max_pages) int32 maps row
    b's logical page i to a physical page, so rows only consume HBM for
    pages they actually hold, and N rows sharing a prompt prefix can map
    their leading logical pages to ONE physical copy
    (``serving.paged.PageAllocator`` owns the mapping + refcounts).

    Physical page 0 is the reserved NULL page: it is never allocated, and
    a freed row's table is all-zeros — its inert per-round decode writes
    land harmlessly in page 0 instead of a page some other row now owns.

    ``page_size`` is static (pytree aux data), so caches with different
    page sizes hash to different jit buckets.
    """

    layers: tuple   # tuple over pattern positions; kv leaves (G,P,ps,KV,hd)
    page_table: Any  # (B, max_pages) int32 — physical page per logical page
    lengths: Any    # (B,) int32 — per-row number of valid tokens
    page_size: int = 16

    def tree_flatten(self):
        return (self.layers, self.page_table, self.lengths), self.page_size

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, page_size=aux)


def paged_cache_specs(cfg: ModelConfig, batch: int, n_pages: int,
                      page_size: int, max_pages: int,
                      kv_dtype: str = "bf16"):
    """Abstract PagedCache tree. ``n_pages`` physical pages per layer pool
    (page 0 reserved as null); each row addresses up to ``max_pages``
    logical pages (max_pages * page_size = the row's max_len).
    ``kv_dtype="int8"`` adds per-token fp32 scale POOLS
    (G, n_pages, page_size, KV, 1) that page exactly like the data.

    Only attention-only patterns page: SSM state is O(1) per row (nothing
    to page), and mixed patterns would need a second cache layout — the
    serving layer keeps those on the dense shared cache.
    """
    for spec in cfg.pattern:
        if not spec.mixer.startswith("attn"):
            raise ValueError(
                f"paged KV caches require an attention-only pattern; mixer "
                f"{spec.mixer!r} has no paged layout (use the dense cache)")
    g = cfg.n_groups
    shape = (g, n_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    if kv_dtype == "int8":
        kv = jax.ShapeDtypeStruct(shape, jnp.int8)
        sc = jax.ShapeDtypeStruct(shape[:-1] + (1,), jnp.float32)
        layer = {"k": kv, "v": kv, "k_scale": sc, "v_scale": sc}
    else:
        kv = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        layer = {"k": kv, "v": kv}
    return PagedCache(
        layers=tuple(dict(layer) for _ in cfg.pattern),
        page_table=jax.ShapeDtypeStruct((batch, max_pages), jnp.int32),
        lengths=jax.ShapeDtypeStruct((batch,), jnp.int32),
        page_size=page_size)


def init_paged_cache(cfg: ModelConfig, batch: int, n_pages: int,
                     page_size: int, max_pages: int,
                     kv_dtype: str = "bf16"):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        paged_cache_specs(cfg, batch, n_pages, page_size, max_pages,
                          kv_dtype))


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def prefill(cfg: ModelConfig, run: RunConfig, params, *, tokens=None,
            embeddings=None, max_len: Optional[int] = None):
    """Returns (last-token logits (B,V), populated Cache)."""
    ref = tokens if tokens is not None else embeddings
    b, s = ref.shape[0], ref.shape[1]
    if max_len is None:
        # `is None`, not falsy: max_len=0 must NOT silently become
        # s + cache_pad — it is a caller bug and raises below.
        max_len = s + run.cache_pad
    if max_len < s:
        raise ValueError(
            f"max_len={max_len} cannot hold the {s}-token prompt")
    positions = jnp.arange(s)[None, :]
    x = _embed_in(cfg, params, tokens, embeddings, positions)

    def group(carry, gp):
        x, aux = carry
        caches = []
        for spec, p in zip(cfg.pattern, gp):
            h = apply_norm(cfg, p["norm1"], x)
            if spec.mixer.startswith("attn"):
                h, (k, v) = attn_lib.attn_forward(
                    cfg, p["attn"], h, mixer=spec.mixer, positions=positions,
                    impl=run.attn_impl, return_kv=True)
                pad = [(0, 0), (0, max_len - s), (0, 0), (0, 0)]
                if run.kv_dtype == "int8":
                    from repro.kernels.decode_attention.quant import \
                        quantize_kv
                    kq, ks = quantize_kv(k)
                    vq, vs = quantize_kv(v)
                    caches.append({"k": jnp.pad(kq, pad),
                                   "v": jnp.pad(vq, pad),
                                   "k_scale": jnp.pad(ks, pad),
                                   "v_scale": jnp.pad(vs, pad)})
                else:
                    caches.append({"k": jnp.pad(k.astype(jnp.bfloat16), pad),
                                   "v": jnp.pad(v.astype(jnp.bfloat16), pad)})
            else:
                h, sc = ssm_lib.ssm_forward(cfg, cfg.ssm, p["ssm"], h,
                                            return_state=True)
                caches.append(sc)
            if cfg.sandwich_norms:
                h = apply_norm(cfg, p["post_norm1"], h)
            x = x + h
            if spec.mlp != "none":
                h = apply_norm(cfg, p["norm2"], x)
                if spec.mlp == "moe":
                    h, a = moe_lib.moe_apply(cfg, cfg.moe, p["moe"], h,
                                             impl=run.moe_impl)
                    aux = aux + a["lb_loss"]
                else:
                    h = mlp_lib.mlp_apply(cfg, p["mlp"], h)
                if cfg.sandwich_norms:
                    h = apply_norm(cfg, p["post_norm2"], h)
                x = x + h
        return (x, aux), tuple(caches)

    (x, _), layer_caches = jax.lax.scan(
        group, (x, jnp.zeros((), jnp.float32)), params["blocks"])
    x_last = apply_norm(cfg, params["final_norm"], x[:, -1])
    logits = _lm_head(cfg, params, x_last)
    return logits, Cache(layers=layer_caches,
                         lengths=jnp.full((b,), s, jnp.int32))


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

# an attention layer's cache leaves, in the order its decode returns them
# (the scales only for an int8 cache)
_KV_KEYS = ("k", "v", "k_scale", "v_scale")


def decode_step(cfg: ModelConfig, run: RunConfig, params, cache: Cache,
                token=None, embedding=None):
    """One decode step. token: (B,1) int32 (or embedding (B,1,D)).

    Returns (logits (B,V), new Cache with every row's length+1). The
    batch is RAGGED: row b embeds/writes/attends at its own position
    ``cache.lengths[b]``, so one dispatch serves continuous-batching
    slots at different depths (a freed row just decodes inertly against
    its masked cache — the serving layer discards its token).

    The cache lives in the scan CARRY (not xs/ys) as the per-pattern
    layer stacks (G, ...). Each attention layer writes the new token's K
    and V straight into its stack at (g, b, lengths[b]) — one scatter of
    B token rows, which XLA applies to the donated carry in place — and
    attends over layer g read from the updated stack, so no layer's cache
    is copied out and written back: a step's cache traffic is one token's
    write per row plus the attention read. SSM state (O(1) per row) is
    still sliced out of its stack and written back whole.
    """
    paged = isinstance(cache, PagedCache)
    lengths = cache.lengths
    pos = lengths[:, None]  # (B,1) — per-row positions
    x = _embed_in(cfg, params, token, embedding, pos)

    def group(carry, gp):
        x, layers, g = carry
        new_layers = []
        for spec, p, c in zip(cfg.pattern, gp, layers):
            h = apply_norm(cfg, p["norm1"], x)
            if spec.mixer.startswith("attn"):
                kw = dict(mixer=spec.mixer, impl=run.attn_impl, layer=g,
                          k_scale=c.get("k_scale"), v_scale=c.get("v_scale"))
                if paged:
                    out = attn_lib.attn_decode_layer_paged(
                        cfg, p["attn"], h, c["k"], c["v"], cache.page_table,
                        lengths, page_size=cache.page_size, **kw)
                else:
                    out = attn_lib.attn_decode_layer(
                        cfg, p["attn"], h, c["k"], c["v"], lengths, **kw)
                h, *kv = out  # k, v, then the scales of an int8 cache
                new_layers.append(dict(zip(_KV_KEYS, kv)))
            else:
                sc = jax.tree.map(
                    lambda t: jax.lax.dynamic_index_in_dim(
                        t, g, 0, keepdims=False), c)
                h, nc = ssm_lib.ssm_decode(cfg, cfg.ssm, p["ssm"], h, sc)
                new_layers.append(jax.tree.map(
                    lambda full, new: jax.lax.dynamic_update_index_in_dim(
                        full, new.astype(full.dtype), g, 0), c, nc))
            if cfg.sandwich_norms:
                h = apply_norm(cfg, p["post_norm1"], h)
            x = x + h
            if spec.mlp != "none":
                h = apply_norm(cfg, p["norm2"], x)
                if spec.mlp == "moe":
                    h, _ = moe_lib.moe_apply(cfg, cfg.moe, p["moe"], h,
                                             impl=run.moe_impl)
                else:
                    h = mlp_lib.mlp_apply(cfg, p["mlp"], h)
                if cfg.sandwich_norms:
                    h = apply_norm(cfg, p["post_norm2"], h)
                x = x + h
        return (x, tuple(new_layers), g + 1), None

    (x, new_layers, _), _ = jax.lax.scan(
        group, (x, cache.layers, jnp.zeros((), jnp.int32)),
        params["blocks"])
    x = apply_norm(cfg, params["final_norm"], x)
    logits = _lm_head(cfg, params, x[:, 0])
    if paged:
        # every row's device length advances, including FREE rows — their
        # zeroed table routes the inert write to null page 0.
        return logits, PagedCache(layers=new_layers,
                                  page_table=cache.page_table,
                                  lengths=lengths + 1,
                                  page_size=cache.page_size)
    return logits, Cache(layers=new_layers, lengths=lengths + 1)


def extend_paged(cfg: ModelConfig, run: RunConfig, params, cache: PagedCache,
                 row, tokens):
    """Chunked prefill-with-history for ONE row of a PagedCache.

    tokens: (1, L) int32 occupying logical positions
    ``start .. start+L-1`` where ``start = cache.lengths[row]``. This is
    the single admission primitive of the paged serving path — ONE
    dispatch whether the row is cold (start=0, L = full prompt) or warm
    (start = shared-prefix length, L = the divergent suffix): the chunk's
    queries attend causally over [history ++ chunk], so a warm admission
    reads the shared prefix pages instead of recomputing them.

    ``row`` is a traced scalar — one compiled executable serves every
    slot. Returns (last-token logits (1, V), cache with
    ``lengths[row] = start + L``).
    """
    L = tokens.shape[1]
    row = jnp.asarray(row, jnp.int32)
    start = jax.lax.dynamic_index_in_dim(cache.lengths, row, 0,
                                         keepdims=False)
    table_row = jax.lax.dynamic_index_in_dim(cache.page_table, row, 0,
                                             keepdims=False)
    positions = start + jnp.arange(L)[None, :]
    x = _embed_in(cfg, params, tokens, None, positions)

    def group(carry, gp):
        x, layers, g = carry
        new_layers = []
        for spec, p, c in zip(cfg.pattern, gp, layers):
            h = apply_norm(cfg, p["norm1"], x)
            # paged_cache_specs guarantees an attention-only pattern
            out = attn_lib.attn_extend_layer_paged(
                cfg, p["attn"], h, c["k"], c["v"], table_row, start,
                mixer=spec.mixer, page_size=cache.page_size, layer=g,
                k_scale=c.get("k_scale"), v_scale=c.get("v_scale"))
            h, *kv = out  # k, v, then the scales of an int8 cache
            new_layers.append(dict(zip(_KV_KEYS, kv)))
            if cfg.sandwich_norms:
                h = apply_norm(cfg, p["post_norm1"], h)
            x = x + h
            if spec.mlp != "none":
                h = apply_norm(cfg, p["norm2"], x)
                if spec.mlp == "moe":
                    h, _ = moe_lib.moe_apply(cfg, cfg.moe, p["moe"], h,
                                             impl=run.moe_impl)
                else:
                    h = mlp_lib.mlp_apply(cfg, p["mlp"], h)
                if cfg.sandwich_norms:
                    h = apply_norm(cfg, p["post_norm2"], h)
                x = x + h
        return (x, tuple(new_layers), g + 1), None

    (x, new_layers, _), _ = jax.lax.scan(
        group, (x, cache.layers, jnp.zeros((), jnp.int32)),
        params["blocks"])
    x = apply_norm(cfg, params["final_norm"], x[:, -1])
    logits = _lm_head(cfg, params, x)
    new_lengths = jax.lax.dynamic_update_index_in_dim(
        cache.lengths, start + L, row, 0)
    return logits, PagedCache(layers=new_layers,
                              page_table=cache.page_table,
                              lengths=new_lengths,
                              page_size=cache.page_size)
