"""Fused sampling epilogue: sampled token ids out of the decode dispatch.

The hot-path finding this spends (BENCH_8): in host-sampler serving every
decode round round-trips the full (B, V) fp32 logits through HBM to a
SEPARATE sampler dispatch (``serving/sampler.py``). Fusing the sampler
into the decode executable's epilogue makes the per-round device traffic
one (B,) int32 token vector instead — still exactly one decode dispatch
per round, now with ZERO sampler dispatches.

Three layers, all with bit-identical semantics to the host sampler:

  * :func:`apply_filters` — the CANONICAL temperature / top-k / top-p
    filter math. ``serving.sampler.sample`` is defined as
    ``categorical(key, apply_filters(logits, ...))``, so parity between
    the fused and host paths is by construction, not by test luck.
  * :func:`fused_sample_kernel` — the Pallas TPU epilogue kernel: one
    program per block of 8 batch rows does temperature scaling, an
    in-kernel top-k threshold (a count-above-threshold ``while_loop`` —
    NO vocab sort, and it reproduces ``jax.lax.top_k``'s duplicate/tie
    semantics), the top-p nucleus mask, and the Gumbel-argmax draw. Two
    inputs the kernel cannot produce portably are computed by XLA ops
    INSIDE the same jit executable and passed in: the per-row nucleus cutoff
    probability (needs a vocab sort) and the Gumbel noise (must come
    from ``jax.random`` so the draw matches the host sampler's
    ``categorical`` bit-for-bit — ``categorical(key, z)`` IS
    ``argmax(z + gumbel(key, z.shape, z.dtype))``).
  * :func:`fused_sample` — the dispatch-level entry point the serving
    engine embeds in its decode executables. On TPU it runs the Pallas
    epilogue; elsewhere (CPU CI, interpret-unfriendly paths, under a
    mesh where the logits arrive vocab-sharded) it lowers to the exact
    host-sampler jnp graph — same executable, same tokens.

Numerics note (the PR-3 fp-near-tie precedent): the jnp fallback is
EXACTLY the host sampler, so off-TPU parity is exact at a fixed key. The
Pallas kernel recomputes softmax with its own reduction order, so on
real TPU a token sitting exactly on the nucleus cutoff may flip; the
interpret-mode parity tests pin the math, and BENCH_8 documents flips.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Canonical filter math (shared by the host sampler and the fused path)
# ---------------------------------------------------------------------------


def apply_filters(logits, *, temperature: float,
                  top_k: Optional[int] = None,
                  top_p: Optional[float] = None):
    """Temperature / top-k / top-p filtered logits, (B, V) -> (B, V).

    Requires ``temperature > 0`` (greedy argmax never filters). Filter
    order is k then p — the usual serving order:

    * ``top_k`` keeps the k highest logits per row (ties at the k-th
      value are ALL kept, matching ``jax.lax.top_k``'s threshold);
    * ``top_p`` keeps the smallest prefix of the probability-sorted
      vocab whose mass reaches ``top_p``; boundary ties are kept and
      the top slot always survives (``top_p <= 0`` degenerates to the
      per-row argmax; ``top_p >= 1`` is a no-op).

    Masked slots are set to ``-1e30``.
    """
    logits = logits / temperature
    if top_k is not None:
        vals, _ = jax.lax.top_k(logits, top_k)
        kth = vals[:, -1:]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    if top_p is not None and top_p < 1.0:
        cutoff = nucleus_cutoff(logits, top_p)
        probs = jax.nn.softmax(logits, axis=-1)
        logits = jnp.where(probs < cutoff, NEG_INF, logits)
    return logits


def nucleus_cutoff(logits, top_p: float):
    """Per-row top-p cutoff probability, (B, V) -> (B, 1) fp32.

    The smallest probability inside the nucleus of the (already
    temperature/top-k filtered) ``logits``: a sorted slot is in the
    nucleus iff the mass strictly BEFORE it is < ``top_p``, with the top
    slot forced in so the nucleus is never empty. This is the one piece
    of the sampler that needs a vocab SORT, which has no reliable Mosaic
    lowering — so the fused path computes it with XLA ops inside the
    same decode executable and hands the kernel one scalar per row.
    """
    probs = jax.nn.softmax(logits, axis=-1)
    sorted_probs = -jnp.sort(-probs, axis=-1)           # descending
    cum = jnp.cumsum(sorted_probs, axis=-1)
    in_nucleus = (cum - sorted_probs) < top_p
    in_nucleus = in_nucleus.at[:, 0].set(True)
    return jnp.min(jnp.where(in_nucleus, sorted_probs, jnp.inf),
                   axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# Pallas epilogue kernel
# ---------------------------------------------------------------------------


def _topk_threshold(z, k: int):
    """Per-row k-th largest value of ``z`` (R, V) WITHOUT sorting, (R, 1).

    Iterates (t, n) where ``n = count(z >= t)`` per row: start at the row
    max and walk t down to the next distinct value until at least k
    entries clear it. A row that has reached k keeps its threshold while
    the others walk on. Terminates in <= k steps (each step admits >= 1
    new entry per unfinished row), each step a vector compare+reduce —
    O(kV) worst case, no sort. With duplicates the returned threshold
    equals ``jax.lax.top_k(z, k)[0][..., -1]``: the count may exceed k,
    and every tie at the threshold survives the ``z < t`` mask — exactly
    the host sampler's semantics.
    """
    fmin = jnp.finfo(jnp.float32).min

    def count_ge(t):
        return jnp.sum((z >= t).astype(jnp.int32), axis=1, keepdims=True)

    t0 = jnp.max(z, axis=1, keepdims=True)

    def cond(carry):
        _, n = carry
        return jnp.any(n < k)

    def body(carry):
        t, n = carry
        t2 = jnp.max(jnp.where(z < t, z, fmin), axis=1, keepdims=True)
        t = jnp.where(n < k, t2, t)
        return t, count_ge(t)

    t, _ = jax.lax.while_loop(cond, body, (t0, count_ge(t0)))
    return t


def _sample_kernel(logits_ref, gumbel_ref, cutoff_ref, tok_ref, *,
                   temperature: float, top_k: Optional[int],
                   use_top_p: bool):
    """A block of rows: filter logits in VMEM, Gumbel-argmax, emit int32.

    The (R, V) logits tile never leaves VMEM — the only HBM write is the
    sampled token ids. ``gumbel_ref`` carries the ``jax.random`` noise
    and ``cutoff_ref`` the per-row nucleus cutoff (see module docstring
    for why those two are produced outside the kernel body).
    """
    z = logits_ref[...].astype(jnp.float32) / temperature  # (R, V)
    if top_k is not None:
        z = jnp.where(z < _topk_threshold(z, top_k), NEG_INF, z)
    if use_top_p:
        # same softmax form as jax.nn.softmax: exp(z - max) / sum
        e = jnp.exp(z - jnp.max(z, axis=1, keepdims=True))
        p = e / jnp.sum(e, axis=1, keepdims=True)
        z = jnp.where(p < cutoff_ref[...], NEG_INF, z)
    y = z + gumbel_ref[...].astype(jnp.float32)
    # argmax = FIRST index attaining the max (2D iota per the TPU rule)
    idx = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    hit = y == jnp.max(y, axis=1, keepdims=True)
    tok_ref[...] = jnp.min(jnp.where(hit, idx, jnp.iinfo(jnp.int32).max),
                           axis=1, keepdims=True)


ROW_BLOCK = 8            # sublane multiple: the block's second-minor dim
VMEM_LIMIT = 96 * 2**20  # v5e has 128 MiB of VMEM; the default scope is 16


@functools.partial(
    jax.jit,
    static_argnames=("temperature", "top_k", "use_top_p", "interpret"))
def fused_sample_kernel(logits, gumbel, cutoff, *, temperature: float,
                        top_k: Optional[int] = None,
                        use_top_p: bool = False, interpret: bool = False):
    """Pallas sampling epilogue. logits/gumbel: (B, V); cutoff: (B, 1)
    fp32 (ignored unless ``use_top_p``). Returns (B,) int32 token ids.
    Requires ``temperature > 0`` (greedy is a plain argmax — no kernel).

    One program samples ``ROW_BLOCK`` rows over the whole vocab (the
    top-k walk and the nucleus softmax need the full row); B is padded
    to a multiple of ``ROW_BLOCK`` and the pad rows are dropped. At
    vocab 152,064 a block is 4.9 MB per fp32 input, double-buffered, plus
    the filter's temporaries — over the default scoped VMEM, so the
    kernel asks for ``VMEM_LIMIT``.
    """
    b, v = logits.shape
    pad = (-b) % ROW_BLOCK
    cutoff = jnp.asarray(cutoff, jnp.float32)
    if pad:
        logits, gumbel, cutoff = (jnp.pad(x, ((0, pad), (0, 0)))
                                  for x in (logits, gumbel, cutoff))
    kernel = functools.partial(_sample_kernel, temperature=temperature,
                               top_k=top_k, use_top_p=use_top_p)
    row = pl.BlockSpec((ROW_BLOCK, v), lambda bi: (bi, 0))
    col = pl.BlockSpec((ROW_BLOCK, 1), lambda bi: (bi, 0))
    out = pl.pallas_call(
        kernel,
        grid=((b + pad) // ROW_BLOCK,),
        in_specs=[row, row, col],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((b + pad, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="fused_sampling_epilogue",
    )(logits, gumbel, cutoff)
    return out[:b, 0]


# ---------------------------------------------------------------------------
# Dispatch-level entry point (what Engine embeds in decode executables)
# ---------------------------------------------------------------------------


def fused_sample(logits, key, *, temperature: float = 0.0,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 use_kernel: Optional[bool] = None,
                 interpret: bool = False):
    """Sample (B, V) logits -> (B,) int32 INSIDE the caller's executable.

    Traced into the decode jit by ``Engine.decode_sample`` /
    ``prefill_into_sample`` / ``extend_row_sample``, so the sampled
    tokens come out of the same dispatch as the decode step and the
    logits never round-trip through HBM to a separate sampler dispatch.

    ``use_kernel=None`` auto-selects: the Pallas epilogue on TPU, the
    exact host-sampler jnp graph elsewhere (CPU CI and mesh-sharded
    logits — the engine forces the jnp path under a mesh, where the
    vocab dim arrives sharded over "model"). At a fixed ``key`` the jnp
    path is BIT-IDENTICAL to ``serving.sampler.sample``; the kernel path
    is the same draw with the filter math moved into VMEM.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel and not interpret:
        filtered = apply_filters(logits, temperature=temperature,
                                 top_k=top_k, top_p=top_p)
        return jax.random.categorical(key, filtered, axis=-1
                                      ).astype(jnp.int32)
    z = logits / temperature
    if top_k is not None:
        vals, _ = jax.lax.top_k(z, top_k)
        z = jnp.where(z < vals[:, -1:], NEG_INF, z)
    use_top_p = top_p is not None and top_p < 1.0
    cutoff = (nucleus_cutoff(z, top_p) if use_top_p
              else jnp.zeros((logits.shape[0], 1), jnp.float32))
    gumbel = jax.random.gumbel(key, logits.shape, logits.dtype)
    return fused_sample_kernel(logits, gumbel, cutoff,
                               temperature=temperature, top_k=top_k,
                               use_top_p=use_top_p, interpret=interpret)
