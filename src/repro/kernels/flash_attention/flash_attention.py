"""Fused flash-attention Pallas TPU kernel (prefill / training path).

Design (TPU-native, see DESIGN.md §5):
  * grid = (batch, q_heads, nQ, nK); the trailing nK axis is "arbitrary"
    (sequential) so the online-softmax running state lives in VMEM scratch
    across k-blocks.
  * Every tile is (block_q, D) of q/out or (block_k, D) of k/v: a
    sequence tile by one head. Mosaic refuses a (block, 1, D) tile over
    a (B, S, H, D) array (an extent-1 head dim second-minor), so the
    head dim leaves the tile's last two dims one of two ways. When D is
    a multiple of 128 the arrays are viewed as (B, S, H*D) lane slabs —
    a free reshape, the layout the decode kernels use — and head h is
    lane block h. Otherwise the wrapper transposes to head-major
    (B, H, S, D) and back (prefill activations, not the KV cache).
    block_q/block_k default 128 to align the MXU contraction lanes.
  * GQA is handled in the k/v index_map (kv_head = q_head // group) — no
    repeated-KV materialization in HBM.
  * Causal / sliding-window masks are applied from global iota offsets;
    fully-masked k-blocks still run (masked) — the ops.py wrapper chooses
    grid bounds so the causal tail is the only waste.
  * Accumulation (m, l, acc) in fp32 regardless of input dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_q: int, block_k: int, n_k: int,
            causal: bool, window: Optional[int], softcap: Optional[float],
            t_valid: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)  # (bq, D)
    k = k_ref[...].astype(jnp.float32)  # (bk, D)
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = cols < t_valid  # padded key columns are never attended
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                      # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)          # (bq, 1)
    p = jnp.exp(s - m_new)                   # (bq, bk)
    # fully-masked rows: m_new stays NEG_INF -> p = exp(0) = 1; kill those
    p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
    alpha = jnp.where(m_prev > NEG_INF / 2, alpha, 0.0)

    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _done():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # rows with no visible keys -> 0 out
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret", "t_valid"))
def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False,
                           t_valid: Optional[int] = None):
    """q: (B,S,H,D); k,v: (B,T,KV,D). S % block_q == 0, T % block_k == 0."""
    b, s, h, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    group = h // kv
    n_q, n_k = s // block_q, t // block_k
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k, n_k=n_k,
        causal=causal, window=window, softcap=softcap,
        t_valid=t_valid if t_valid is not None else t)

    if d % 128 == 0:       # lane slabs: (B, S, H*D), head h = lane block h
        q_spec = pl.BlockSpec((None, block_q, d),
                              lambda bi, hi, qi, ki: (bi, qi, hi))
        kv_spec = pl.BlockSpec((None, block_k, d),
                               lambda bi, hi, qi, ki: (bi, ki, hi // group))
        out_shape = (b, s, h * d)
        args = (q.reshape(b, s, h * d), k.reshape(b, t, kv * d),
                v.reshape(b, t, kv * d))
    else:                  # head-major: (B, H, S, D)
        q_spec = pl.BlockSpec((None, None, block_q, d),
                              lambda bi, hi, qi, ki: (bi, hi, qi, 0))
        kv_spec = pl.BlockSpec((None, None, block_k, d),
                               lambda bi, hi, qi, ki: (bi, hi // group, ki, 0))
        out_shape = (b, h, s, d)
        args = (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3))
    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(*args)
    if d % 128 == 0:
        return out.reshape(b, s, h, d)
    return out.transpose(0, 2, 1, 3)
