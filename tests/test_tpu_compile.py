"""Compile-only guards: every Pallas kernel of the served path, compiled
for a TPU v5e chip at published widths — without a chip.

The TPU compiler is installed wherever jaxlib's TPU support is, and it
compiles for a described (not attached) topology. Interpret-mode parity
tests cannot see what Mosaic refuses (unaligned block shapes, scoped
VMEM overruns); these can. Nothing runs, so they say nothing about
results or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and a test
worker that describes it keeps it until exit.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_kernel, decode_attention_partials_kernel,
    decode_attention_quant_kernel, paged_decode_attention_kernel,
    paged_decode_attention_quant_kernel)
from repro.kernels.decode_attention.fused_sampling import fused_sample_kernel
from repro.kernels.flash_attention.flash_attention import (
    flash_attention_kernel)

# qwen2-7b attention widths; B and T as a serving decode batch
B, T, H, KV, D = 8, 4096, 28, 4, 128
VOCAB = 152_064
PAGE, N_PAGES, MAX_PAGES = 128, 257, 32


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile written to the persistent cache cannot be
    # read back without a chip; keep these out of any cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, chip, *shapes, **kw):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    compiled = jax.jit(lambda *a: fn(*a, **kw)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


BF, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.mark.parametrize("b,t,h,kv,d", [
    (B, T, H, KV, D),          # qwen2-7b
    (B, 512, 12, 12, 64),      # distilbert-imdb widths (two heads a slab)
], ids=["qwen2-7b", "distilbert"])
def test_decode_attention_compiles(chip, b, t, h, kv, d):
    _compile(decode_attention_kernel, chip, ((b, h, d), BF),
             ((b, t, kv, d), BF), ((b, t, kv, d), BF), ((b,), I32))


def test_decode_attention_quant_compiles(chip):
    _compile(decode_attention_quant_kernel, chip, ((B, H, D), BF),
             ((B, T, KV, D), I8), ((B, T, KV, D), I8),
             ((B, T, KV, 1), F32), ((B, T, KV, 1), F32), ((B,), I32))


def test_decode_attention_partials_compiles(chip):
    _compile(decode_attention_partials_kernel, chip, ((B, H, D), BF),
             ((B, T // 4, KV, D), BF), ((B, T // 4, KV, D), BF),
             ((2, B), I32))


def test_paged_decode_attention_compiles(chip):
    _compile(paged_decode_attention_kernel, chip, ((B, H, D), BF),
             ((N_PAGES, PAGE, KV, D), BF), ((N_PAGES, PAGE, KV, D), BF),
             ((B,), I32), ((B, MAX_PAGES), I32))


def test_paged_decode_attention_quant_compiles(chip):
    _compile(paged_decode_attention_quant_kernel, chip, ((B, H, D), BF),
             ((N_PAGES, PAGE, KV, D), I8), ((N_PAGES, PAGE, KV, D), I8),
             ((N_PAGES, PAGE, KV, 1), F32), ((N_PAGES, PAGE, KV, 1), F32),
             ((B,), I32), ((B, MAX_PAGES), I32))


@pytest.mark.parametrize("s,h,kv,d", [
    (2048, H, KV, D),          # qwen2-7b: lane slabs (D % 128 == 0)
    (512, 12, 12, 64),         # distilbert-imdb: head-major
], ids=["qwen2-7b", "distilbert"])
def test_flash_attention_compiles(chip, s, h, kv, d):
    _compile(flash_attention_kernel, chip, ((1, s, h, d), BF),
             ((1, s, kv, d), BF), ((1, s, kv, d), BF))


@pytest.mark.parametrize("top_k,use_top_p", [(None, False), (50, True)])
def test_fused_sample_compiles(chip, top_k, use_top_p):
    _compile(fused_sample_kernel, chip, ((B, VOCAB), F32),
             ((B, VOCAB), F32), ((B, 1), F32), temperature=0.8,
             top_k=top_k, use_top_p=use_top_p)
