"""GQA attention: full-sequence (train/prefill) and single-token decode paths.

Supports: grouped-query attention, causal / bidirectional / sliding-window
masks, logit softcapping (Gemma-2), QKV / output biases (Qwen-2, Whisper),
RoPE or external positions, and cross-attention (encoder-decoder).

``impl`` dispatch:
  * "xla"       — pure jnp einsum path (reference; what the dry-run lowers)
  * "pallas"    — fused Pallas TPU kernels (kernels/flash_attention, decode)
  * "seq_shard" — decode over a KV cache whose SEQUENCE dim is sharded
                  over "model" (dist.collectives.seq_sharded_*; the
                  per-shard block is itself the Pallas decode kernel on
                  TPU). The cache must be in the
                  ``dist.sharding.cache_shardings(..., seq_shard=True)``
                  layout — ``serving.Engine(seq_shard=True)`` pins it.

Sharding expectations (all mesh-optional — no mesh means replicated):
activations arrive batch-sharded over the data axes; caches arrive in the
``cache_shardings`` layout (kv-heads over "model" by default, seq over
"model" under seq_shard); every constraint here goes through
``dist.context.constrain`` so unsatisfiable axes drop instead of erroring.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist import context as dctx
from repro.models.common import AxSpec, ModelConfig, apply_rope, softcap

NEG_INF = -1e30


def _constrain_heads_or_seq(x):
    """(B,S,H,hd): shard heads over "model" when divisible, else fall back
    to sequence parallelism (shard S) so attention compute still
    partitions (e.g. qwen2's 28 heads on a 16-wide model axis)."""
    h = x.shape[2]
    msize = dctx.axis_size("model")
    if msize > 1 and h % msize == 0:
        return dctx.constrain(x, None, "model", None)
    return dctx.constrain(x, "model", None, None)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attn_specs(cfg: ModelConfig, *, cross: bool = False, d_in: Optional[int] = None):
    d = d_in or cfg.d_model
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # q and k contract over d_model, not over the second-to-last dim the
    # default fan-in rule reads (heads): at that scale the initial scores
    # are d_model/heads times too large and softmax starts one-hot, so a
    # bf16 rounding flips which key wins
    p = {
        "wq": AxSpec((d, h, hd), ("d_model", "heads", "head_dim"),
                     scale=d ** -0.5),
        "wk": AxSpec((d, kv, hd), ("d_model", "kv_heads", "head_dim"),
                     scale=d ** -0.5),
        "wv": AxSpec((d, kv, hd), ("d_model", "kv_heads", "head_dim")),
        "wo": AxSpec((h, hd, d), ("heads", "head_dim", "d_model")),
    }
    if cfg.qkv_bias:
        p["bq"] = AxSpec((h, hd), ("heads", "head_dim"), "zeros")
        p["bk"] = AxSpec((kv, hd), ("kv_heads", "head_dim"), "zeros")
        p["bv"] = AxSpec((kv, hd), ("kv_heads", "head_dim"), "zeros")
    if cfg.attn_out_bias:
        p["bo"] = AxSpec((d,), ("d_model",), "zeros")
    if cross:
        # cross-attention keys/values come from the encoder stream
        de = cfg.enc_d_model or d
        p["wk"] = AxSpec((de, kv, hd), ("d_model", "kv_heads", "head_dim"),
                         scale=de ** -0.5)
        p["wv"] = AxSpec((de, kv, hd), ("d_model", "kv_heads", "head_dim"))
    return p


def project_qkv(cfg: ModelConfig, p, x, kv_x=None):
    """x: (B,S,D) -> q (B,S,H,hd), k/v (B,T,KV,hd)."""
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", kv_x, p["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", kv_x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return q, k, v


def out_proj(p, o):
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    if "bo" in p:
        y = y + p["bo"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Core attention math (XLA reference path)
# ---------------------------------------------------------------------------


def _mask_full(sq: int, st: int, mask_kind: str, window: Optional[int],
               q_offset=0):
    """(sq, st) boolean mask. q position i attends kv position j."""
    qi = jnp.arange(sq)[:, None] + q_offset
    kj = jnp.arange(st)[None, :]
    if mask_kind == "bidir":
        m = jnp.ones((sq, st), bool)
    else:
        m = kj <= qi
    if window is not None:
        m = m & (kj > qi - window)
    return m


def _attend_dense(q, k, v, *, mask_kind, window, cap, q_offset=0):
    """Unfused reference attention for one q block vs full k/v."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hd)
    scale = 1.0 / (hd ** 0.5)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = softcap(logits, cap)
    mask = _mask_full(sq, k.shape[1], mask_kind, window, q_offset)
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return o.reshape(b, sq, h, hd).astype(q.dtype)


Q_CHUNK = 1024  # q-block size for the memory-bounded XLA path


def attend_full(q, k, v, *, mask_kind: str = "causal",
                window: Optional[int] = None, cap: Optional[float] = None,
                impl: str = "xla"):
    """q: (B,S,H,hd); k,v: (B,T,KV,hd). GQA-aware; returns (B,S,H,hd).

    The XLA path chunks the query dimension (scan over Q_CHUNK blocks) so
    logits never materialize at (S,T) — the memory-efficient-attention
    fallback for when the Pallas flash kernel isn't available (CPU
    dry-runs). Long-sequence cells are impossible without this.
    """
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(
            q, k, v, causal=(mask_kind == "causal"), window=window,
            softcap=cap)
    b, s, h, hd = q.shape
    t = k.shape[1]
    if s <= 2 * Q_CHUNK or s % Q_CHUNK:
        return _attend_dense(q, k, v, mask_kind=mask_kind, window=window,
                             cap=cap)
    nc = s // Q_CHUNK
    qc = jnp.moveaxis(q.reshape(b, nc, Q_CHUNK, h, hd), 1, 0)
    offsets = jnp.arange(nc) * Q_CHUNK

    def body(_, xs):
        qi, off = xs
        qi = _constrain_heads_or_seq(qi)
        o = _attend_dense(qi, k, v, mask_kind=mask_kind, window=window,
                          cap=cap, q_offset=off)
        return None, _constrain_heads_or_seq(o)

    _, oc = jax.lax.scan(body, None, (qc, offsets))
    return jnp.moveaxis(oc, 0, 1).reshape(b, s, h, hd)


def row_lengths(lengths, b: int):
    """Normalize a scalar-or-(B,) ``lengths`` to a (B,) int32 vector."""
    return jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))


def attend_decode(q, k_cache, v_cache, lengths, *,
                  k_scale=None, v_scale=None,
                  window: Optional[int] = None, cap: Optional[float] = None,
                  impl: str = "xla"):
    """Single-token decode. q: (B,1,H,hd); caches: (B,Smax,KV,hd).

    ``lengths`` (int32, scalar or (B,)) = per-row index of the current
    token; row b attends kv positions j <= lengths[b] (the new token's
    k/v must already be written). A (B,) vector makes the batch RAGGED —
    the shared-batched-cache serving path decodes every slot at its own
    position in one dispatch.

    ``k_scale``/``v_scale`` ((B,Smax,KV,1) fp32, both or neither) mark
    the caches as int8 per-token-quantized (``kernels…quant``): the
    Pallas path dequantizes tiles in VMEM; the XLA path pre-dequantizes.
    Not supported under ``seq_shard`` (collectives carry bf16 partials).

    Sharding: q is batch-sharded; under ``impl="seq_shard"`` the caches
    must carry ``NamedSharding`` with the sequence dim over "model" (the
    ``cache_shardings(seq_shard=True)`` layout) — the output returns
    batch-sharded only. Other impls expect kv_heads over "model" at most.
    """
    if impl == "seq_shard":
        if k_scale is not None:
            raise ValueError(
                "int8 KV caches do not support attn_impl='seq_shard' — "
                "use kv_dtype='bf16' with sequence sharding (see "
                "serving/README.md)")
        from repro.dist import collectives
        return collectives.seq_sharded_decode(
            q, k_cache, v_cache, lengths, window=window, cap=cap)
    if impl == "pallas":
        from repro.kernels.decode_attention import ops as da_ops
        return da_ops.decode_attention(
            q[:, 0], k_cache, v_cache, lengths, k_scale=k_scale,
            v_scale=v_scale, window=window, softcap=cap)[:, None]
    if k_scale is not None:
        from repro.kernels.decode_attention.quant import dequantize_kv
        k_cache = dequantize_kv(k_cache, k_scale)
        v_cache = dequantize_kv(v_cache, v_scale)
    b, _, h, hd = q.shape
    kvh = k_cache.shape[2]
    g = h // kvh
    lengths = row_lengths(lengths, b)
    qg = q.reshape(b, kvh, g, hd)
    scale = 1.0 / (hd ** 0.5)
    logits = jnp.einsum("bkgh,btkh->bkgt", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    logits = softcap(logits, cap)
    t = jnp.arange(k_cache.shape[1])
    mask = t[None, :] <= lengths[:, None]  # (B, Smax)
    if window is not None:
        mask = mask & (t[None, :] > (lengths[:, None] - window))
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgt,btkh->bkgh", probs, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Layer-level wrappers used by the transformer block
# ---------------------------------------------------------------------------


def attn_forward(cfg: ModelConfig, p, x, *, mixer: str, positions,
                 impl: str = "xla", mask_kind: str = "causal",
                 return_kv: bool = False):
    """Full-sequence attention sublayer (no residual/norm — block handles).

    x arrives batch-sharded (and seq-over-"model" under Megatron-SP);
    q/k/v are re-constrained to heads-or-seq over "model" internally, so
    callers never pre-shard projections. ``return_kv`` hands back the
    unpadded (k, v) for prefill cache construction.
    """
    q, k, v = project_qkv(cfg, p, x)
    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = _constrain_heads_or_seq(q)
    k = dctx.constrain(k, None, "model", None)  # kv heads when divisible
    v = dctx.constrain(v, None, "model", None)
    window = cfg.window if mixer == "attn_local" else None
    o = attend_full(q, k, v, mask_kind=mask_kind, window=window,
                    cap=cfg.attn_softcap, impl=impl)
    y = dctx.constrain(out_proj(p, o), None, None)
    return (y, (k, v)) if return_kv else y


def write_kv_rows(cache, new, lengths, layer=None):
    """Write ``new`` (B,1,KV,hd) at each row's own position ``lengths[b]``
    as one scatter of B token rows, so a step's HBM traffic for the write
    is one token per row.

    ``cache`` is one layer's (B,Smax,KV,hd), or with ``layer`` the decode
    step's layer stack (G,B,Smax,KV,hd), written at (layer, b,
    lengths[b]): the stack is updated where it lies (in place in the
    scan carry) and never copied out and back. A row whose position falls
    outside [0, Smax) writes nothing.
    """
    b, smax = new.shape[0], cache.shape[-3]
    pos = row_lengths(lengths, b)
    # a negative index would wrap round: send it past the end, dropped
    pos = jnp.where((pos >= 0) & (pos < smax), pos, smax)
    idx = (jnp.arange(b), pos)
    if layer is not None:
        idx = (layer,) + idx
    return cache.at[idx].set(new[:, 0].astype(cache.dtype), mode="drop")


def layer_view(t, layer):
    """Layer ``layer`` of a (G, ...) cache stack, for reading only. A
    per-layer cache (``layer`` None) or an absent leaf passes through."""
    if t is None or layer is None:
        return t
    return jax.lax.dynamic_index_in_dim(t, layer, 0, keepdims=False)


def attn_decode_layer(cfg: ModelConfig, p, x, k_cache, v_cache, lengths, *,
                      mixer: str, impl: str = "xla",
                      k_scale=None, v_scale=None, layer=None):
    """Decode sublayer: project, write new kv at each row's ``lengths[b]``,
    attend.

    Returns (y, new_k_cache, new_v_cache) — the caches come back in the
    layout they arrived in. ``lengths`` is scalar or (B,): per-row
    positions let one shared batched cache serve rows at different decode
    depths (the ragged batch of ``serving.ContinuousBatcher``). Under
    ``impl="seq_shard"`` each row's write happens inside the shard that
    owns its global position (fused with the attention in one shard_map),
    so SPMD never gathers the cache around the update; other impls write
    with one scatter (:func:`write_kv_rows`).

    With ``layer`` the caches are the decode step's layer stacks
    (G,B,Smax,KV,hd): the new token lands at (layer, b, lengths[b]) in
    the stack itself and attention reads layer ``layer`` of the updated
    stack, so no layer's cache is copied out and written back.

    When ``k_scale``/``v_scale`` ((B,Smax,KV,1) fp32 scale caches, or
    their layer stacks) are given the kv caches are int8: the new token's
    post-RoPE k/v are quantized per token, both the int8 values and the
    scales are written at ``lengths[b]``, and the return grows to the
    5-tuple (y, k_cache, v_cache, k_scale, v_scale) — callers that never
    pass scales keep the 3-tuple contract unchanged.
    """
    b = x.shape[0]
    lengths = row_lengths(lengths, b)
    q, k, v = project_qkv(cfg, p, x)  # q,k,v: (B,1,·,hd)
    if cfg.pos == "rope":
        pos = lengths[:, None]  # (B,1): each row rotates at its own index
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    window = cfg.window if mixer == "attn_local" else None
    if impl == "seq_shard":
        if k_scale is not None:
            raise ValueError(
                "int8 KV caches do not support attn_impl='seq_shard' — "
                "use kv_dtype='bf16' with sequence sharding (see "
                "serving/README.md)")
        # fused write+attend over the sequence-sharded cache (shard_map):
        # the write must happen shard-locally or SPMD gathers the cache.
        from repro.dist import collectives
        o, k_cache, v_cache = collectives.seq_sharded_write_decode(
            q, k, v, k_cache, v_cache, lengths, window=window,
            cap=cfg.attn_softcap, layer=layer)
        return out_proj(p, o), k_cache, v_cache
    if k_scale is not None:
        from repro.kernels.decode_attention.quant import quantize_kv
        k, ks_new = quantize_kv(k)   # (B,1,KV,hd) int8, (B,1,KV,1) fp32
        v, vs_new = quantize_kv(v)
        k_scale = write_kv_rows(k_scale, ks_new, lengths, layer)
        v_scale = write_kv_rows(v_scale, vs_new, lengths, layer)
    k_cache = write_kv_rows(k_cache, k, lengths, layer)
    v_cache = write_kv_rows(v_cache, v, lengths, layer)
    o = attend_decode(q, layer_view(k_cache, layer),
                      layer_view(v_cache, layer), lengths,
                      k_scale=layer_view(k_scale, layer),
                      v_scale=layer_view(v_scale, layer),
                      window=window, cap=cfg.attn_softcap, impl=impl)
    if k_scale is not None:
        return out_proj(p, o), k_cache, v_cache, k_scale, v_scale
    return out_proj(p, o), k_cache, v_cache


# ---------------------------------------------------------------------------
# Block-paged KV cache layers (page-table indirection; see serving/paged.py
# for the allocator that owns the physical pages and their refcounts)
# ---------------------------------------------------------------------------


def write_kv_pages(pool, new, page_table, lengths, page_size: int, layer):
    """Write ``new`` (B,1,KV,hd) into layer ``layer`` of the shared page
    pool at each row's own logical position ``lengths[b]``, resolved
    through its page table: one scatter into the stack, in place.

    pool: the layer stack (G, P, page_size, KV, hd). The serving layer
    guarantees (via the allocator's copy-on-write barrier) that no two
    ACTIVE rows resolve their write position to the same physical page;
    free rows all write into the reserved null page 0, which is never
    allocated.
    """
    b = new.shape[0]
    lengths = row_lengths(lengths, b)
    pmax = page_table.shape[1]
    slot = jnp.clip(lengths // page_size, 0, pmax - 1)
    pages = jnp.take_along_axis(page_table, slot[:, None], axis=1)[:, 0]
    offs = lengths % page_size
    return pool.at[layer, pages, offs].set(new[:, 0].astype(pool.dtype))


def attend_decode_paged(q, k_pages, v_pages, page_table, lengths, *,
                        k_scale=None, v_scale=None,
                        window: Optional[int] = None,
                        cap: Optional[float] = None, impl: str = "xla"):
    """Single-token decode through a paged KV cache. q: (B,1,H,hd);
    pools: (P, ps, KV, hd); page_table: (B, Pmax) int32.

    ``impl="pallas"`` reads KV tiles through the page table inside the
    kernel's index map (no dense view ever materializes); the XLA path
    gathers each row's logical view first — correctness fallback, not
    the memory win. ``k_scale``/``v_scale`` ((P, ps, KV, 1) fp32 scale
    pools, both or neither) mark the pools as int8 per-token-quantized;
    scale pages ride the same page-table indirection as the data.
    ``seq_shard`` is NOT supported on the paged path (the serving layer
    falls back to the dense cache under seq-shard; documented in
    serving/README.md).
    """
    if impl == "seq_shard":
        raise ValueError(
            "paged KV caches do not support attn_impl='seq_shard' — the "
            "serving layer uses the dense shared cache under seq-shard "
            "(see serving/README.md)")
    b = q.shape[0]
    lengths = row_lengths(lengths, b)
    if impl == "pallas":
        from repro.kernels.decode_attention import ops as da_ops
        return da_ops.paged_decode_attention(
            q[:, 0], k_pages, v_pages, lengths, page_table,
            k_scale=k_scale, v_scale=v_scale, window=window,
            softcap=cap)[:, None]
    from repro.kernels.decode_attention.ref import gather_pages
    if k_scale is not None:
        from repro.kernels.decode_attention.quant import dequantize_kv
        k_pages = dequantize_kv(k_pages, k_scale)
        v_pages = dequantize_kv(v_pages, v_scale)
    k = gather_pages(k_pages, page_table)
    v = gather_pages(v_pages, page_table)
    return attend_decode(q, k, v, lengths, window=window, cap=cap,
                         impl="xla")


def attn_decode_layer_paged(cfg: ModelConfig, p, x, k_pages, v_pages,
                            page_table, lengths, *, mixer: str,
                            page_size: int, layer, impl: str = "xla",
                            k_scale=None, v_scale=None):
    """Paged counterpart of :func:`attn_decode_layer`: project, write the
    new kv through each row's page table into layer ``layer`` of the pool
    stacks (G, P, ps, KV, hd), attend through the same table over that
    layer. Returns (y, new_k_pages, new_v_pages) — or, when ``k_scale``/
    ``v_scale`` scale pools are given (int8 pools), the 5-tuple
    (y, k_pages, v_pages, k_scale, v_scale) with the new token's
    post-RoPE k/v quantized and its scales written through the SAME page
    table (so COW copies and shared prefixes carry scales with data)."""
    b = x.shape[0]
    lengths = row_lengths(lengths, b)
    q, k, v = project_qkv(cfg, p, x)
    if cfg.pos == "rope":
        pos = lengths[:, None]
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    window = cfg.window if mixer == "attn_local" else None
    if k_scale is not None:
        from repro.kernels.decode_attention.quant import quantize_kv
        k, ks_new = quantize_kv(k)
        v, vs_new = quantize_kv(v)
        k_scale = write_kv_pages(k_scale, ks_new, page_table, lengths,
                                 page_size, layer)
        v_scale = write_kv_pages(v_scale, vs_new, page_table, lengths,
                                 page_size, layer)
    k_pages = write_kv_pages(k_pages, k, page_table, lengths, page_size,
                             layer)
    v_pages = write_kv_pages(v_pages, v, page_table, lengths, page_size,
                             layer)
    o = attend_decode_paged(q, layer_view(k_pages, layer),
                            layer_view(v_pages, layer), page_table, lengths,
                            k_scale=layer_view(k_scale, layer),
                            v_scale=layer_view(v_scale, layer),
                            window=window, cap=cfg.attn_softcap, impl=impl)
    if k_scale is not None:
        return out_proj(p, o), k_pages, v_pages, k_scale, v_scale
    return out_proj(p, o), k_pages, v_pages


def attn_extend_layer_paged(cfg: ModelConfig, p, x, k_pages, v_pages,
                            table_row, start, *, mixer: str,
                            page_size: int, layer, k_scale=None,
                            v_scale=None):
    """Chunked prefill-with-history for ONE paged row, into layer
    ``layer`` of the pool stacks (G, P, ps, KV, hd).

    x: (1, L, D) — the chunk occupies logical positions
    ``start .. start+L-1`` of the row whose page table is ``table_row``
    (Pmax,); positions < start already hold valid KV (possibly
    SHARED prefix pages written by an earlier request — this read is
    what makes warm-prefix prefill skip the prefix compute entirely).
    Writes the chunk's KV through the table, then attends the L queries
    over [history ++ chunk] causally (``q_offset=start``). Always the
    XLA gather path — a fused Pallas chunked-prefill kernel is future
    work; the decode hot loop is where the paged kernel lives.
    Returns (y (1,L,D), new_k_pages, new_v_pages) — the 5-tuple with
    scale pools appended when ``k_scale``/``v_scale`` are given (int8
    pools: the chunk's post-RoPE k/v quantize per token before writing).
    """
    L = x.shape[1]
    positions = start + jnp.arange(L)[None, :]
    q, k, v = project_qkv(cfg, p, x)
    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    pos = start + jnp.arange(L)
    pmax = table_row.shape[0]
    slot = jnp.clip(pos // page_size, 0, pmax - 1)
    pages = table_row[slot]
    offs = pos % page_size
    if k_scale is not None:
        from repro.kernels.decode_attention.quant import quantize_kv
        k, ks_new = quantize_kv(k)   # (1,L,KV,hd) int8, (1,L,KV,1) fp32
        v, vs_new = quantize_kv(v)
        k_scale = k_scale.at[layer, pages, offs].set(ks_new[0])
        v_scale = v_scale.at[layer, pages, offs].set(vs_new[0])
    k_pages = k_pages.at[layer, pages, offs].set(k[0].astype(k_pages.dtype))
    v_pages = v_pages.at[layer, pages, offs].set(v[0].astype(v_pages.dtype))
    from repro.kernels.decode_attention.ref import gather_pages
    kl, vl = layer_view(k_pages, layer), layer_view(v_pages, layer)
    if k_scale is not None:
        from repro.kernels.decode_attention.quant import dequantize_kv
        kl = dequantize_kv(kl, layer_view(k_scale, layer))
        vl = dequantize_kv(vl, layer_view(v_scale, layer))
    kr = gather_pages(kl, table_row[None])  # (1, Pmax*ps, KV, hd)
    vr = gather_pages(vl, table_row[None])
    window = cfg.window if mixer == "attn_local" else None
    o = _attend_dense(q, kr.astype(q.dtype), vr.astype(q.dtype),
                      mask_kind="causal", window=window,
                      cap=cfg.attn_softcap, q_offset=start)
    if k_scale is not None:
        return out_proj(p, o), k_pages, v_pages, k_scale, v_scale
    return out_proj(p, o), k_pages, v_pages


def cross_attn_forward(cfg: ModelConfig, p, x, enc_k, enc_v, *,
                       impl: str = "xla"):
    """Decoder cross-attention against precomputed encoder K/V."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
    o = attend_full(q, enc_k, enc_v, mask_kind="bidir", cap=cfg.attn_softcap,
                    impl="xla" if impl == "seq_shard" else impl)
    return out_proj(p, o)


def cross_kv(cfg: ModelConfig, p, enc_out):
    k = jnp.einsum("btd,dhk->bthk", enc_out, p["wk"].astype(enc_out.dtype))
    v = jnp.einsum("btd,dhk->bthk", enc_out, p["wv"].astype(enc_out.dtype))
    if "bk" in p:
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return k, v
