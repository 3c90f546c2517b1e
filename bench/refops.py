"""Plain float32 building blocks of the references, in ``jax.numpy``.

No kernels, no cache, no batching tricks; every matrix product runs at
``highest`` precision (the caller traces under
``jax.default_matmul_precision("highest")``). ``fp8`` is the control's
fake quantization: both operands of every weight product rounded to
float8 e4m3 with a scale per output channel (weights) or per row
(activations), then multiplied in float32: the step below the
configurations' bfloat16 that would tempt a later change.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30


E4M3_MAX = 448.0


def fp8(x, contract_axes):
    """Round ``x`` to float8 e4m3: one scale per index of the axes that
    are not contracted, max |x| over the contracted ones mapped to the
    format's largest value."""
    amax = jnp.max(jnp.abs(x), axis=contract_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def activations(quant: bool):
    """What a product's activation operand goes through: nothing for the
    reference, ``fp8`` per row (over the last ``n`` axes) for the
    control."""
    if not quant:
        return lambda x, n=1: x
    return lambda x, n=1: fp8(x, tuple(range(-n, 0)))


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def layer_norm(x, gain, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def rope(x, theta):
    """Rotary embedding by halves (the published Qwen2 form). x: (S,H,D)."""
    s, _, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, causal: bool):
    """Softmax attention of one sequence. q (S,H,D), k/v (S,KV,D); each
    group of H/KV query heads shares one key/value head."""
    s, h, d = q.shape
    kvh = k.shape[1]
    qg = q.reshape(s, kvh, h // kvh, d)
    logits = jnp.einsum("skgd,tkd->kgst", qg, k) / jnp.sqrt(float(d))
    if causal:
        mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        logits = jnp.where(mask, logits, NEG)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("kgst,tkd->skgd", p, v).reshape(s, h, d)
