"""Flash-decode Pallas TPU kernel: one query token per row vs a long,
possibly RAGGED, batched KV cache.

Design:
  * grid = (batch, kv lane blocks, nT): the KV sequence is split into
    ``block_t``-sized VMEM tiles; the trailing axis is sequential and the
    (m, l, acc) online-softmax state lives in VMEM scratch across tiles.
  * Tiles the TPU compiler accepts. A cache (B, T, KV, D) is viewed as
    (B, T, KV*D) — a free reshape — and one KV tile is a (block_t, W)
    lane slab holding ``hb`` consecutive kv heads, W = hb*D. ``hb`` is 1
    when D is a multiple of 128 (qwen2: W = 128), 128/D when the heads
    pack a 128-lane slab exactly (distilbert's D=64: two heads per slab),
    else all KV heads (W = KV*D, the full trailing dim). Either way the
    block's last two dims are (8k, 128k) or full, which Mosaic requires;
    a (block_t, 1, D) tile over the KV dim is refused.
  * All ``group = H/KV`` query heads of the ``hb`` kv heads in a slab are
    the rows of one (hb*group, W) matmul operand. With hb > 1 the query
    block is block-diagonal — row group h carries its head's D values in
    lanes [h*D, (h+1)*D) and zeros elsewhere — so ``q @ k^T`` contracts
    each query only with its own kv head's lanes. ``p @ v`` produces all
    W lanes per row; the wrapper keeps each row group's own D lanes.
    At hb = 1 this is the plain (group, D) GQA matmul.
  * Per-row bounds arrive via PrefetchScalarGridSpec as a (2, B) array of
    (upper, lower): row b attends columns ``lower < col <= upper``. The
    KV index map clamps the tile index at the row's last valid tile —
    tiles strictly past ``upper`` re-read the last valid tile (no DMA)
    and are fully masked, so a short row in a ragged batch costs about
    ``upper`` of HBM traffic, not ``Smax``.
  * int8 caches carry fp32 per-token-per-head scales, viewed as
    (B, T, KV); the scale tile is (block_t, KV) (KV is the full trailing
    dim) and the kernel expands this slab's scale columns over its lanes
    and dequantizes the int8 tile in VMEM, so HBM traffic stays int8.
  * Paged caches (P, page_size, KV, D) use the same kernel with a second
    prefetched operand, the (B, Pmax) page table: the KV index map sends
    logical tile ``ti`` of row b to physical page ``table[b, ti]``.

The partials variant emits the raw (acc, l, m) online-softmax state
instead of normalizing at the last tile: ``dist.collectives`` combines
those per-shard partials of a sequence-sharded cache with one pmax and
two psums.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
LANES = 128


def lane_heads(kv: int, d: int) -> int:
    """kv heads per (block_t, hb*D) KV tile: the fewest whose lane width
    is a multiple of 128, else all of them (the full trailing dim)."""
    if d % LANES == 0:
        return 1
    if LANES % d == 0 and kv % (LANES // d) == 0:
        return LANES // d
    return kv


def _block_diag_q(q, kv: int, hb: int):
    """(B, H, D) -> (B, KV/hb, hb*G, hb*D): row group h of a slab holds
    query heads of kv head h in lanes [h*D, (h+1)*D), zeros elsewhere."""
    b, h, d = q.shape
    g = h // kv
    q5 = q.reshape(b, kv // hb, hb, g, d)
    if hb == 1:
        return q5.reshape(b, kv, g, d)
    eye = jnp.eye(hb, dtype=q.dtype)
    qbd = q5[:, :, :, :, None, :] * eye[None, None, :, None, :, None]
    return qbd.reshape(b, kv // hb, hb * g, hb * d)


def _own_lanes(o, hb: int, g: int, d: int):
    """(B, KV/hb, hb*G, hb*D) -> (B, KV, G, D): each row group's own
    head lanes (the diagonal of the block-diagonal product)."""
    b, nkb = o.shape[:2]
    if hb == 1:
        return o.reshape(b, nkb, g, d)
    o6 = o.reshape(b, nkb, hb, g, hb, d)
    od = jnp.diagonal(o6, axis1=2, axis2=4)          # (B, nkb, G, D, hb)
    return jnp.moveaxis(od, -1, 2).reshape(b, nkb * hb, g, d)


def _lane_scales(sc, kb, hb: int, d: int):
    """(block_t, KV) per-head scales -> the scales of slab ``kb``'s heads,
    as (block_t, 1) when hb == 1, else expanded to (block_t, hb*D)."""
    bt, kv = sc.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bt, kv), 1)

    def column(head):
        return jnp.sum(jnp.where(col == head, sc, 0.0), axis=1,
                       keepdims=True)

    if hb == 1:
        return column(kb)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bt, hb * d), 1)
    out = jnp.zeros((bt, hb * d), jnp.float32)
    for h in range(hb):
        own = (lane >= h * d) & (lane < (h + 1) * d)
        out = jnp.where(own, column(kb * hb + h), out)
    return out


def _clamp_tile(ti, last_valid, block_t: int):
    """Clamp tile index ``ti`` at the tile holding ``last_valid``.

    Used inside KV index maps: tiles past a row's own upper bound re-read
    the row's last valid tile instead of streaming dead KV from HBM (the
    re-read is free — Pallas skips the DMA when the block index repeats —
    and the in-kernel column mask zeroes any contribution).
    """
    return jnp.minimum(ti, jnp.maximum(last_valid, 0) // block_t)


def _kernel(*refs, n_prefetch: int, quant: bool, partials: bool,
            scale: float, block_t: int, n_t: int, hb: int, d: int,
            softcap: Optional[float]):
    bounds_ref = refs[0]
    refs = refs[n_prefetch:]
    q_ref, k_ref, v_ref = refs[:3]
    refs = refs[3:]
    if quant:
        ks_ref, vs_ref = refs[:2]
        refs = refs[2:]
    n_out = 3 if partials else 1
    outs, (m_scr, l_scr, acc_scr) = refs[:n_out], refs[n_out:]

    bi = pl.program_id(0)
    kb = pl.program_id(1)
    ti = pl.program_id(2)
    upper = bounds_ref[0, bi]
    lower = bounds_ref[1, bi]

    @pl.when(ti == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)              # (rows, W)
    k = k_ref[...].astype(jnp.float32)              # (block_t, W)
    v = v_ref[...].astype(jnp.float32)
    if quant:
        k = k * _lane_scales(ks_ref[...], kb, hb, d)
        v = v * _lane_scales(vs_ref[...], kb, hb, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    cols = ti * block_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where((cols <= upper) & (cols > lower), s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_new), 0.0)
    p = jnp.where(m_new > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ti == n_t - 1)
    def _done():
        if partials:
            o_ref, m_ref, l_ref = outs
            o_ref[...] = acc_scr[...]
            m_ref[...] = m_scr[...]
            l_ref[...] = l_scr[...]
        else:
            l = l_scr[...]
            l = jnp.where(l == 0.0, 1.0, l)
            outs[0][...] = (acc_scr[...] / l).astype(outs[0].dtype)


def _flash_decode(q, k, v, k_scale, v_scale, bounds, table, *,
                  block_t: int, softcap: Optional[float], partials: bool,
                  interpret: bool, name: str):
    """Shared pallas_call for every decode variant.

    q: (B,H,D); k/v: (N, T, KV, D) — N = B for a dense cache, the page
    pool size for a paged one (then ``table`` is (B, Pmax) and
    ``block_t`` is the page size); scales: (N, T, KV, 1) fp32 or None;
    bounds: (2, B) int32 per-row (upper, lower). Returns (B, KV, G, D)
    in q's dtype, or fp32 (acc (B,KV,G,D), l (B,KV,G), m (B,KV,G)) when
    ``partials``.
    """
    b, h, d = q.shape
    n, t, kv = k.shape[:3]
    g = h // kv
    hb = lane_heads(kv, d)
    nkb, rows, w = kv // hb, hb * g, hb * d
    paged = table is not None
    n_t = table.shape[1] if paged else t // block_t
    quant = k_scale is not None

    def tile(bi, ti, pref):
        ti = _clamp_tile(ti, pref[0][0, bi], block_t)
        return (pref[1][bi, ti], 0) if paged else (bi, ti)

    def kv_map(bi, kb, ti, *pref):
        return tile(bi, ti, pref) + (kb,)

    def scale_map(bi, kb, ti, *pref):
        return tile(bi, ti, pref) + (0,)

    def row_map(bi, kb, ti, *pref):
        return (bi, kb, 0, 0)

    kv_spec = pl.BlockSpec((None, block_t, w), kv_map)
    row_spec = pl.BlockSpec((None, None, rows, w), row_map)
    in_specs = [row_spec, kv_spec, kv_spec]
    operands = [_block_diag_q(q, kv, hb), k.reshape(n, t, kv * d),
                v.reshape(n, t, kv * d)]
    if quant:
        in_specs += [pl.BlockSpec((None, block_t, kv), scale_map)] * 2
        operands += [k_scale.reshape(n, t, kv), v_scale.reshape(n, t, kv)]
    if partials:
        col_spec = pl.BlockSpec((None, None, rows, 1), row_map)
        out_specs = [row_spec, col_spec, col_spec]
        out_shape = [jax.ShapeDtypeStruct((b, nkb, rows, w), jnp.float32),
                     jax.ShapeDtypeStruct((b, nkb, rows, 1), jnp.float32),
                     jax.ShapeDtypeStruct((b, nkb, rows, 1), jnp.float32)]
    else:
        out_specs = row_spec
        out_shape = jax.ShapeDtypeStruct((b, nkb, rows, w), q.dtype)
    prefetch = [jnp.asarray(bounds, jnp.int32)]
    if paged:
        prefetch.append(jnp.asarray(table, jnp.int32))

    kernel = functools.partial(
        _kernel, n_prefetch=len(prefetch), quant=quant, partials=partials,
        scale=1.0 / (d ** 0.5), block_t=block_t, n_t=n_t, hb=hb, d=d,
        softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, nkb, n_t),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, w), jnp.float32)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*prefetch, *operands)
    if not partials:
        return _own_lanes(out, hb, g, d)
    acc, m, l = out
    return (_own_lanes(acc, hb, g, d), l.reshape(b, kv, g),
            m.reshape(b, kv, g))


def _length_bounds(lengths, window: Optional[int]):
    """(B,) current indices -> (2, B) (upper, lower) column bounds."""
    lengths = jnp.asarray(lengths, jnp.int32)
    lower = (lengths - window if window is not None
             else jnp.full_like(lengths, -2 ** 30))
    return jnp.stack([lengths, lower])


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "block_t", "interpret"))
def decode_attention_kernel(q, k_cache, v_cache, lengths, *,
                            window: Optional[int] = None,
                            softcap: Optional[float] = None,
                            block_t: int = 512, interpret: bool = False):
    """q: (B,H,D); caches: (B,T,KV,D), T % block_t == 0; lengths: (B,)
    int32 — row b attends kv positions <= lengths[b]."""
    b, h, d = q.shape
    out = _flash_decode(q, k_cache, v_cache, None, None,
                        _length_bounds(lengths, window), None,
                        block_t=block_t, softcap=softcap, partials=False,
                        interpret=interpret, name="decode_attention")
    return out.reshape(b, h, d)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "block_t", "interpret"))
def decode_attention_quant_kernel(q, k_cache, v_cache, k_scale, v_scale,
                                  lengths, *, window: Optional[int] = None,
                                  softcap: Optional[float] = None,
                                  block_t: int = 512,
                                  interpret: bool = False):
    """int8-KV flash decode. q: (B,H,D) fp; caches: (B,T,KV,D) int8;
    scales: (B,T,KV,1) fp32 (per-token-per-kv-head); lengths: (B,) int32.
    The scale tiles use the same clamped index map as the KV tiles, so a
    short row's HBM traffic stays ~lengths[b] of int8 bytes + scales."""
    b, h, d = q.shape
    out = _flash_decode(q, k_cache, v_cache, k_scale, v_scale,
                        _length_bounds(lengths, window), None,
                        block_t=block_t, softcap=softcap, partials=False,
                        interpret=interpret, name="decode_attention_int8_kv")
    return out.reshape(b, h, d)


@functools.partial(
    jax.jit, static_argnames=("softcap", "block_t", "interpret"))
def decode_attention_partials_kernel(q, k_cache, v_cache, bounds, *,
                                     softcap: Optional[float] = None,
                                     block_t: int = 512,
                                     interpret: bool = False):
    """Partial-softmax flash decode over one local KV block.

    q: (B,H,D); caches: (B,T,KV,D) with T % block_t == 0; ``bounds``:
    (2, B) int32 — per-row (upper, lower) LOCAL column bounds (row b
    attends columns iff ``lower[b] < col <= upper[b]``; the caller folds
    the shard offset and any sliding window into them). Returns fp32
    ``(num (B,KV,G,D), den (B,KV,G), m (B,KV,G))`` matching
    ``decode_attention_partials_ref``.
    """
    return _flash_decode(q, k_cache, v_cache, None, None, bounds, None,
                         block_t=block_t, softcap=softcap, partials=True,
                         interpret=interpret,
                         name="decode_attention_partials")


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "interpret"))
def paged_decode_attention_kernel(q, k_pages, v_pages, lengths, page_table,
                                  *, window: Optional[int] = None,
                                  softcap: Optional[float] = None,
                                  interpret: bool = False):
    """Flash decode through a block-paged KV cache.

    q: (B,H,D); pools: (P, page_size, KV, D) — ONE physical page pool
    shared by every row (and, under copy-on-write prefix sharing, by
    several rows at once); page_table: (B, Pmax) int32 — row b's logical
    page i lives at physical page ``page_table[b, i]``; lengths: (B,)
    int32 — row b attends LOGICAL positions <= lengths[b].

    The KV tile is one page: the grid's trailing axis walks logical
    pages and the KV index map reads the scalar-prefetched page table to
    DMA the matching physical page, clamped at the row's last valid page
    (the same per-row HBM early exit as the dense ragged kernel). Rows
    sharing prefix pages DMA the SAME physical tiles; no dense per-row
    view ever materializes.
    """
    b, h, d = q.shape
    out = _flash_decode(q, k_pages, v_pages, None, None,
                        _length_bounds(lengths, window), page_table,
                        block_t=k_pages.shape[1], softcap=softcap,
                        partials=False, interpret=interpret,
                        name="paged_decode_attention")
    return out.reshape(b, h, d)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "interpret"))
def paged_decode_attention_quant_kernel(q, k_pages, v_pages, k_scale,
                                        v_scale, lengths, page_table, *,
                                        window: Optional[int] = None,
                                        softcap: Optional[float] = None,
                                        interpret: bool = False):
    """int8-KV paged flash decode. q: (B,H,D); pools: (P, ps, KV, D)
    int8; scale pools: (P, ps, KV, 1) fp32 — each physical page carries
    its own per-token scale rows, so the scale DMA routes through the
    SAME scalar-prefetched page table (and COW page copies / shared
    prefix pages move scales with their data for free)."""
    b, h, d = q.shape
    out = _flash_decode(q, k_pages, v_pages, k_scale, v_scale,
                        _length_bounds(lengths, window), page_table,
                        block_t=k_pages.shape[1], softcap=softcap,
                        partials=False, interpret=interpret,
                        name="paged_decode_attention_int8_kv")
    return out.reshape(b, h, d)
