"""Single-device parity for repro.dist.collectives.

``seq_sharded_write_decode``'s math (cache write at ``length``, masking,
GQA head grouping, sliding window, softcap) is pinned against the
decode-attention oracle on the mesh-free fallback path — the 8-device
shard_map path is pinned against the same oracle in
test_dist_and_dryrun.py, so the two tiers together cover both branches.
``compress_psum`` round-trip error is bounded on a one-device mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.collectives import (compress_psum, seq_sharded_decode,
                                    seq_sharded_write_decode)
from repro.kernels.decode_attention.ref import decode_attention_ref


def _inputs(b=2, s=64, h=8, kv=2, d=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    kn = jax.random.normal(ks[1], (b, 1, kv, d))
    vn = jax.random.normal(ks[2], (b, 1, kv, d))
    kc = jax.random.normal(ks[3], (b, s, kv, d))
    vc = jax.random.normal(ks[4], (b, s, kv, d))
    return q, kn, vn, kc, vc


@pytest.mark.parametrize("length", [0, 1, 37, 63])
def test_write_decode_matches_reference(length):
    q, kn, vn, kc, vc = _inputs()
    o, nk, nv = seq_sharded_write_decode(q, kn, vn, kc, vc,
                                         jnp.int32(length))
    kc2 = kc.at[:, length].set(kn[:, 0])
    vc2 = vc.at[:, length].set(vn[:, 0])
    oref = decode_attention_ref(q[:, 0], kc2, vc2, jnp.int32(length))[:, None]
    assert float(jnp.max(jnp.abs(o - oref))) < 1e-5
    # the cache write is exact, not approximate
    assert float(jnp.max(jnp.abs(np.array(nk) - np.array(kc2)))) == 0.0
    assert float(jnp.max(jnp.abs(np.array(nv) - np.array(vc2)))) == 0.0


@pytest.mark.parametrize("window,cap", [(16, None), (None, 30.0),
                                        (8, 20.0)])
def test_write_decode_window_and_softcap(window, cap):
    q, kn, vn, kc, vc = _inputs(seed=1)
    length = jnp.int32(50)
    o, _, _ = seq_sharded_write_decode(q, kn, vn, kc, vc, length,
                                       window=window, cap=cap)
    kc2 = kc.at[:, 50].set(kn[:, 0])
    vc2 = vc.at[:, 50].set(vn[:, 0])
    oref = decode_attention_ref(q[:, 0], kc2, vc2, length,
                                window=window, softcap=cap)[:, None]
    assert float(jnp.max(jnp.abs(o - oref))) < 1e-5


def test_write_decode_gqa_head_grouping():
    # kv == h (MHA) and kv == 1 (MQA) bracket the grouped case
    for kv in (1, 4):
        q, kn, vn, kc, vc = _inputs(h=4, kv=kv, seed=2)
        length = jnp.int32(10)
        o, nk, nv = seq_sharded_write_decode(q, kn, vn, kc, vc, length)
        kc2 = kc.at[:, 10].set(kn[:, 0])
        vc2 = vc.at[:, 10].set(vn[:, 0])
        oref = decode_attention_ref(q[:, 0], kc2, vc2, length)[:, None]
        assert float(jnp.max(jnp.abs(o - oref))) < 1e-5


def test_seq_sharded_decode_matches_reference_without_mesh():
    q, _, _, kc, vc = _inputs(seed=3)
    length = jnp.int32(40)
    o = seq_sharded_decode(q, kc, vc, length)
    oref = decode_attention_ref(q[:, 0], kc, vc, length)[:, None]
    assert float(jnp.max(jnp.abs(o - oref))) < 1e-5


# ---------------------------------------------------------------------------
# compress_psum
# ---------------------------------------------------------------------------


def _one_device_psum(x, method):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("pod",))
    from jax.sharding import PartitionSpec as P
    f = jax.shard_map(lambda v: compress_psum(v, "pod", method),
                      mesh=mesh, in_specs=P(), out_specs=P(),
                      axis_names={"pod"}, check_vma=False)
    return jax.jit(f)(x)


def test_compress_psum_int8_round_trip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 64), jnp.float32)
    out = _one_device_psum(x, "int8")
    # one-device psum == identity up to quantization: |err| <= scale/2
    amax = float(jnp.max(jnp.abs(x)))
    assert float(jnp.max(jnp.abs(out - x))) <= amax / 127.0 / 2 + 1e-7
    assert out.dtype == jnp.float32


def test_compress_psum_bf16_round_trip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 64), jnp.float32)
    out = _one_device_psum(x, "bf16")
    # bf16 has an 8-bit mantissa: relative error <= 2^-8
    err = jnp.abs(out - x) / jnp.maximum(jnp.abs(x), 1e-6)
    assert float(jnp.max(err)) <= 2.0 ** -8
    assert out.dtype == jnp.float32


def test_compress_psum_rejects_unknown_method():
    x = jnp.ones((4,))
    with pytest.raises(ValueError):
        _one_device_psum(x, "fp4")
