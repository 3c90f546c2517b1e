"""Flash-attention Pallas kernel vs pure-jnp oracle (interpret mode).

Sweeps shapes, GQA ratios, dtypes, masks, windows, softcaps — per the
per-kernel allclose requirement.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_attention_ref

CASES = [
    # (b, s, t, h, kv, d, causal, window, cap)
    (1, 128, 128, 4, 2, 64, True, None, None),
    (2, 64, 64, 4, 4, 32, True, None, None),
    (1, 256, 256, 8, 2, 64, True, None, 50.0),
    (1, 128, 128, 4, 1, 64, True, 32, None),
    (2, 64, 128, 4, 2, 64, False, None, None),   # cross attn, t > s
    (1, 100, 100, 8, 2, 64, True, None, None),   # non-multiple: pad path
    (1, 96, 200, 2, 2, 128, False, None, 30.0),  # pad + bidir + cap
    (1, 128, 128, 4, 2, 192, True, None, None),  # nemotron head_dim
    (1, 128, 128, 7, 1, 128, True, None, None),  # qwen2 GQA, lane slabs
    (1, 64, 64, 4, 2, 256, True, 16, None),      # two-vreg-wide slabs
]


@pytest.mark.parametrize("case", CASES, ids=[str(c) for c in CASES])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(case, dtype):
    b, s, t, h, kv, d, causal, window, cap = case
    key = jax.random.PRNGKey(hash(case) % 2**31)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, s, h, d), dtype)
    k = jax.random.normal(k2, (b, t, kv, d), dtype)
    v = jax.random.normal(k3, (b, t, kv, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=cap, block_q=32, block_k=32,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window,
                              softcap=cap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_block_shape_independence():
    """Same result regardless of VMEM tile shape."""
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 128, 4, 64))
    k = jax.random.normal(key, (1, 128, 2, 64))
    v = jax.random.normal(key, (1, 128, 2, 64))
    outs = [
        flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
        for bq, bk in [(32, 32), (64, 32), (32, 64), (128, 128)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5, rtol=1e-5)


def test_fully_masked_rows_are_zero():
    """window=1 + causal: each row sees exactly itself (never NaN)."""
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (1, 64, 2, 32))
    k = jax.random.normal(key, (1, 64, 2, 32))
    v = jax.random.normal(key, (1, 64, 2, 32))
    out = flash_attention(q, k, v, causal=True, window=1, block_q=32,
                          block_k=32, interpret=True)
    assert bool(jnp.all(jnp.isfinite(out)))
    ref = flash_attention_ref(q, k, v, causal=True, window=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
