"""Manual collectives: sequence-sharded decode attention + compressed psum.

``seq_sharded_decode`` / ``seq_sharded_write_decode`` run decode attention
over a KV cache whose SEQUENCE dim is sharded across the "model" axis.
Each shard computes a flash-style partial softmax over its local cache
block (running max, exp-sum, weighted values) and the shards combine with
one pmax + two psums — the cache never materializes unsharded. The write
variant also writes each row's new K/V into whichever shard owns that
row's global position ``lengths[b]``, shard-locally, so SPMD can't decide
to all-gather the cache around the update; given the decode step's layer
stacks and a layer index it writes into the stack in place. ``lengths``
is scalar or (B,) — per-row lengths are what let one shared batched
cache serve ragged continuous-batching rows in a single dispatch.

The per-shard block is the ``kernels/decode_attention`` Pallas kernel
(``decode_attention_partials``) on TPU; off-TPU it runs the identical
pure-jnp math (``decode_attention_partials_ref``) so CPU tests and
dry-runs stay green. ``set_fused_partials`` is a test hook: forcing the
kernel off-TPU runs it in Pallas interpret mode, which is how the parity
tests pin kernel against reference.

Both entry points fall back to the identical single-device math when
there is no ambient mesh, the "model" axis is trivial, or the sequence
doesn't divide — ``tests/test_collectives_ref.py`` pins that fallback
against ``decode_attention_ref``, and the 8-device subprocess test pins
the sharded path against the same oracle.

``compress_psum`` emulates an int8/bf16-compressed gradient all-reduce
over a (DCN) mesh axis inside a partially-manual shard_map.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist import context as ctx

# test hook for the per-shard block: None = the platform decides (kernel
# on TPU), True/False = forced (see set_fused_partials)
_FUSED_OVERRIDE: Optional[bool] = None


def set_fused_partials(enabled: Optional[bool]):
    """Test hook: force the per-shard partial-softmax implementation.

    ``True`` dispatches to the Pallas kernel even off-TPU (interpret
    mode), ``False`` forces the pure-jnp reference, ``None`` restores the
    default: kernel on TPU, jnp elsewhere.
    """
    global _FUSED_OVERRIDE
    _FUSED_OVERRIDE = enabled


def fused_partials_enabled() -> bool:
    if _FUSED_OVERRIDE is not None:
        return _FUSED_OVERRIDE
    return jax.default_backend() == "tpu"


def _partial_decode(q, k_blk, v_blk, lengths, offset, window, cap):
    """Flash-decode partials over one cache block.

    q: (B,1,H,hd); k_blk/v_blk: (B,Sl,KV,hd); global kv position of local
    row t is ``offset + t``; ``lengths`` is scalar or (B,) — per-row
    current indices for ragged batches. Returns (num (B,KV,G,hd),
    den (B,KV,G), m (B,KV,G)) — all fp32 — such that softmax-attention
    over the union of blocks is ``psum(num·e^{m-M}) / psum(den·e^{m-M})``
    with M = pmax(m).

    Dispatches to the fused Pallas kernel when
    :func:`fused_partials_enabled` (interpret mode off-TPU), else to the
    jnp reference — same contract either way.
    """
    from repro.kernels.decode_attention import ops as da_ops
    from repro.kernels.decode_attention import ref as da_ref
    if fused_partials_enabled():
        return da_ops.decode_attention_partials(
            q[:, 0], k_blk, v_blk, lengths, offset=offset, window=window,
            softcap=cap)
    return da_ref.decode_attention_partials_ref(
        q[:, 0], k_blk, v_blk, lengths, offset=offset, window=window,
        softcap=cap)


def _combine_local(q, num, den):
    b, _, h, hd = q.shape
    o = num / jnp.maximum(den, 1e-30)[..., None]
    return o.reshape(b, 1, h, hd).astype(q.dtype)


def _shard_plan(mesh, batch: int, seq: int):
    """(batch_spec_entry, manual_axes) for the decode shard_maps, or None
    when the sequence can't shard over "model".

    The data axes are always MANUAL (batch split when it divides,
    replicated via a None spec when it doesn't), so the shard_map is
    fully manual and ``axis_index("model")`` is a plain manual index —
    batch-of-1 continuous-batching slots on a multi-device data axis
    take the same path as full batches.
    """
    msize = ctx.axis_size("model", mesh)
    if mesh is None or msize <= 1 or seq % msize:
        return None
    dp = ctx.dp_axes(mesh)
    dp = tuple(a for a in dp if a != "model")
    dp_size = 1
    for a in dp:
        dp_size *= int(mesh.shape[a])
    bspec = dp if (dp and batch % dp_size == 0) else None
    manual = frozenset(dp + ("model",))
    return bspec, manual


def seq_sharded_decode(q, k_cache, v_cache, lengths, *,
                       window: Optional[int] = None,
                       cap: Optional[float] = None):
    """Decode attention over a sequence-sharded KV cache.

    q: (B,1,H,hd); caches (B,S,KV,hd) with S sharded over "model";
    ``lengths`` scalar or (B,) — per-row current indices for ragged
    batches. Returns (B,1,H,hd), batch-sharded only. Matches
    ``decode_attention_ref(q[:, 0], k_cache, v_cache, lengths)[:, None]``.
    """
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32),
                               (q.shape[0],))
    plan = _shard_plan(ctx.get_mesh(), q.shape[0], k_cache.shape[1])
    if plan is None:
        num, den, _ = _partial_decode(q, k_cache, v_cache, lengths, 0,
                                      window, cap)
        return _combine_local(q, num, den)
    bspec, manual = plan
    mesh = ctx.get_mesh()
    from jax.sharding import PartitionSpec as P
    rep = P(bspec, None, None, None)
    shc = P(bspec, "model", None, None)

    def body(q, kc, vc, lengths):
        off = jax.lax.axis_index("model") * kc.shape[1]
        num, den, m = _partial_decode(q, kc, vc, lengths, off, window, cap)
        m_g = jax.lax.pmax(m, "model")
        scale = jnp.exp(m - m_g)
        num = jax.lax.psum(num * scale[..., None], "model")
        den = jax.lax.psum(den * scale, "model")
        return _combine_local(q, num, den)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(rep, shc, shc, P(bspec)), out_specs=rep,
        axis_names=manual, check_vma=False)(q, k_cache, v_cache, lengths)


def seq_sharded_write_decode(q, k_new, v_new, k_cache, v_cache, lengths, *,
                             window: Optional[int] = None,
                             cap: Optional[float] = None, layer=None):
    """Fused cache-write + decode attention over a sequence-sharded cache.

    Writes k_new/v_new (B,1,KV,hd) at each row's global position
    ``lengths[b]`` — inside the shard that owns it, as one scatter that
    drops the rows whose position lives on another shard — then attends
    q over the updated cache (row b sees positions <= lengths[b]).
    ``lengths`` is scalar or (B,). The caches are one layer's
    (B,S,KV,hd), or with ``layer`` the decode step's layer stacks
    (G,B,S,KV,hd), written at (layer, b, ·) in place and read at layer
    ``layer``. Returns (out (B,1,H,hd), new_k_cache, new_v_cache); the
    caches keep their sequence-over-"model" sharding.
    """
    if layer is None:  # one layer's cache is a stack of one
        o, kc, vc = seq_sharded_write_decode(
            q, k_new, v_new, k_cache[None], v_cache[None], lengths,
            window=window, cap=cap, layer=0)
        return o, kc[0], vc[0]
    from repro.models.attention import layer_view, write_kv_rows
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32),
                               (q.shape[0],))
    layer = jnp.asarray(layer, jnp.int32)
    plan = _shard_plan(ctx.get_mesh(), q.shape[0], k_cache.shape[2])
    if plan is None:
        kc = write_kv_rows(k_cache, k_new, lengths, layer)
        vc = write_kv_rows(v_cache, v_new, lengths, layer)
        num, den, _ = _partial_decode(q, layer_view(kc, layer),
                                      layer_view(vc, layer), lengths, 0,
                                      window, cap)
        return _combine_local(q, num, den), kc, vc
    bspec, manual = plan
    mesh = ctx.get_mesh()
    from jax.sharding import PartitionSpec as P
    rep = P(bspec, None, None, None)
    shc = P(None, bspec, "model", None, None)

    def body(q, kn, vn, kc, vc, lengths, layer):
        off = jax.lax.axis_index("model") * kc.shape[2]
        kc = write_kv_rows(kc, kn, lengths - off, layer)
        vc = write_kv_rows(vc, vn, lengths - off, layer)
        num, den, m = _partial_decode(q, layer_view(kc, layer),
                                      layer_view(vc, layer), lengths, off,
                                      window, cap)
        m_g = jax.lax.pmax(m, "model")
        scale = jnp.exp(m - m_g)
        num = jax.lax.psum(num * scale[..., None], "model")
        den = jax.lax.psum(den * scale, "model")
        return _combine_local(q, num, den), kc, vc

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, rep, shc, shc, P(bspec), P()),
        out_specs=(rep, shc, shc),
        axis_names=manual, check_vma=False)(
            q, k_new, v_new, k_cache, v_cache, lengths, layer)


# ---------------------------------------------------------------------------
# Compressed gradient reduction
# ---------------------------------------------------------------------------


def compress_psum(x, axis_name: str, method: str):
    """psum over ``axis_name`` with the payload compressed to ``method``.

    Emulates the wire format of a compressed cross-pod (DCN) gradient
    all-reduce; must be called inside a shard_map that is manual over
    ``axis_name``. "bf16" casts the payload; "int8" quantizes against a
    shared per-tensor amax (one extra scalar pmax) and sums in int32 so
    the accumulator can't saturate. Returns fp32. Round-trip error bounds
    are pinned by tests/test_collectives_ref.py.
    """
    if method in (None, "none"):
        return jax.lax.psum(x, axis_name)
    if method == "bf16":
        return jax.lax.psum(x.astype(jnp.bfloat16),
                            axis_name).astype(jnp.float32)
    if method == "int8":
        xf = x.astype(jnp.float32)
        amax = jax.lax.pmax(jnp.max(jnp.abs(xf)), axis_name)
        scale = jnp.maximum(amax, 1e-30) / 127.0
        q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
        total = jax.lax.psum(q.astype(jnp.int32), axis_name)
        return total.astype(jnp.float32) * scale
    raise ValueError(f"unknown grad compression method {method!r}")
