"""The decode step writes each new token into the carried cache stack.

``transformer.decode_step`` carries every attention layer's cache as a
stack over layer groups, (G, B, Smax, KV, hd) per leaf (paged: a pool of
(G, P, page_size, KV, hd)). Each layer scatters the new token's K and V
into the stack at (g, b, lengths[b]) and attends over layer g read from
the updated stack; no layer's cache is sliced out, written and put back.

The parity tests hold that step to a reference kept here, which does
what the step used to do: slice each layer out of the stack, run the
per-layer decode sublayer on it and write the whole layer back. Logits
and caches must be bit-identical over several steps of a ragged batch
with a freed row and a row that runs past the end of the cache. The
lowering tests read the StableHLO: no ``dynamic_update_slice`` may write
a whole layer's cache (or, sequence-sharded, a whole chip's block).
"""
import re
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_in_subprocess
from repro import configs
from repro.models import RunConfig, build
from repro.models import attention as attn_lib
from repro.models import mlp as mlp_lib
from repro.models import transformer as tfm
from repro.models.common import apply_norm

B, SMAX, PAGE = 4, 32, 8
LENGTHS = [0, 5, 17, SMAX - 1]
STEPS = 4
FREED = 1  # this row is freed (length reset to 0) after the first step


def _model():
    cfg = configs.smoke("qwen2-7b")
    model = build(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _random_leaf(key, s):
    if s.dtype == jnp.int8:
        return jax.random.randint(key, s.shape, -127, 128, jnp.int32
                                  ).astype(jnp.int8)
    if s.dtype == jnp.float32:  # int8 scales: positive
        return jax.random.uniform(key, s.shape, jnp.float32, 1e-3, 2e-2)
    return jax.random.normal(key, s.shape, jnp.float32).astype(s.dtype)


def _filled(specs, seed):
    """A cache whose every KV position holds values (not zeros), so a
    write that lands in the wrong place changes what attention reads."""
    leaves, tree = jax.tree.flatten(specs.layers)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return tree.unflatten([_random_leaf(k, s) for k, s in zip(keys, leaves)])


def _cache(form, cfg, model):
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    if form == "paged":
        max_pages = SMAX // PAGE
        n_pages = 1 + B * max_pages  # page 0 is the null page
        specs = model.paged_cache_specs(B, n_pages, PAGE, max_pages)
        table = 1 + jnp.arange(B * max_pages, dtype=jnp.int32).reshape(
            B, max_pages)
        return tfm.PagedCache(layers=_filled(specs, 1), page_table=table,
                              lengths=lengths, page_size=PAGE)
    specs = model.cache_specs(B, SMAX, kv_dtype="int8" if form == "int8"
                              else "bf16")
    return tfm.Cache(layers=_filled(specs, 1), lengths=lengths)


def _free(cache, row):
    """What the serving layer does to a freed row: length 0 (and, paged,
    an all-null page table)."""
    lengths = cache.lengths.at[row].set(0)
    if isinstance(cache, tfm.PagedCache):
        return tfm.PagedCache(layers=cache.layers,
                              page_table=cache.page_table.at[row].set(0),
                              lengths=lengths, page_size=cache.page_size)
    return tfm.Cache(layers=cache.layers, lengths=lengths)


def _reference_step(cfg, params, cache, token):
    """One decode step as the layer-copy form: slice layer g out of each
    stack, decode it, write the whole layer back into the stack."""
    paged = isinstance(cache, tfm.PagedCache)
    lengths = cache.lengths
    x = tfm._embed_in(cfg, params, token, None, lengths[:, None])

    def group(carry, gp):
        x, layers, g = carry
        new = []
        for spec, p, c in zip(cfg.pattern, gp, layers):
            lc = jax.tree.map(lambda t: t[g], c)
            h = apply_norm(cfg, p["norm1"], x)
            if paged:  # one layer as a stack of one, written at layer 0
                lc = jax.tree.map(lambda t: t[None], lc)
                out = attn_lib.attn_decode_layer_paged(
                    cfg, p["attn"], h, lc["k"], lc["v"], cache.page_table,
                    lengths, mixer=spec.mixer, page_size=cache.page_size,
                    layer=0)
                out = (out[0],) + tuple(t[0] for t in out[1:])
            else:
                out = attn_lib.attn_decode_layer(
                    cfg, p["attn"], h, lc["k"], lc["v"], lengths,
                    mixer=spec.mixer, k_scale=lc.get("k_scale"),
                    v_scale=lc.get("v_scale"))
            h, *kv = out
            new.append(jax.tree.map(
                lambda full, one: jax.lax.dynamic_update_index_in_dim(
                    full, one, g, 0),
                c, dict(zip(("k", "v", "k_scale", "v_scale"), kv))))
            x = x + h
            x = x + mlp_lib.mlp_apply(
                cfg, p["mlp"], apply_norm(cfg, p["norm2"], x))
        return (x, tuple(new), g + 1), None

    (x, layers, _), _ = jax.lax.scan(
        group, (x, cache.layers, jnp.zeros((), jnp.int32)),
        params["blocks"])
    logits = tfm._lm_head(cfg, params,
                          apply_norm(cfg, params["final_norm"], x)[:, 0])
    if paged:
        return logits, tfm.PagedCache(layers=layers,
                                      page_table=cache.page_table,
                                      lengths=lengths + 1,
                                      page_size=cache.page_size)
    return logits, tfm.Cache(layers=layers, lengths=lengths + 1)


def _assert_same(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("form", ["dense", "int8", "paged"])
def test_decode_step_matches_layer_copy_reference(form):
    """Ragged rows [0, 5, 17, Smax-1], a freed row, a row past the end:
    the in-place step and the slice-write-back reference agree bit for
    bit on logits and on every cache leaf, step after step."""
    cfg, model, params = _model()
    run = RunConfig()
    step = jax.jit(lambda p, c, t: model.decode_step(run, p, c,
                                                     {"token": t}))
    ref = jax.jit(lambda p, c, t: _reference_step(cfg, p, c, t))
    cache_new = cache_ref = _cache(form, cfg, model)
    toks = jax.random.randint(jax.random.PRNGKey(7), (STEPS, B, 1), 0,
                              cfg.vocab_size)
    for i in range(STEPS):
        logits_new, cache_new = step(params, cache_new, toks[i])
        logits_ref, cache_ref = ref(params, cache_ref, toks[i])
        np.testing.assert_array_equal(np.asarray(logits_new),
                                      np.asarray(logits_ref))
        _assert_same(cache_new, cache_ref)
        if i == 0:
            cache_new, cache_ref = (_free(cache_new, FREED),
                                    _free(cache_ref, FREED))
    # the row that started at Smax-1 ran past the end and kept going
    assert int(cache_new.lengths[3]) == SMAX - 1 + STEPS


def test_row_past_cache_end_is_inert():
    """A row whose position is at or past Smax writes nothing: its cache
    is unchanged, and the other rows' logits and caches are what they are
    with that row anywhere else."""
    cfg, model, params = _model()
    run = RunConfig()
    step = jax.jit(lambda p, c, t: model.decode_step(run, p, c,
                                                     {"token": t}))
    base = _cache("dense", cfg, model)
    tok = jax.random.randint(jax.random.PRNGKey(9), (B, 1), 0,
                             cfg.vocab_size)
    over = tfm.Cache(layers=base.layers,
                     lengths=jnp.asarray([3, SMAX, 7, SMAX + 5], jnp.int32))
    inside = tfm.Cache(layers=base.layers,
                       lengths=jnp.asarray([3, 0, 7, 1], jnp.int32))
    logits_over, after = step(params, over, tok)
    logits_inside, _ = step(params, inside, tok)
    for before_leaf, after_leaf in zip(jax.tree.leaves(base.layers),
                                       jax.tree.leaves(after.layers)):
        before_leaf, after_leaf = np.asarray(before_leaf), np.asarray(
            after_leaf)
        for row in (1, 3):  # past the end: untouched
            np.testing.assert_array_equal(after_leaf[:, row],
                                          before_leaf[:, row])
        for row, pos in ((0, 3), (2, 7)):  # in range: only its position
            changed = np.any(after_leaf[:, row] != before_leaf[:, row],
                             axis=(0, 2, 3))
            assert np.flatnonzero(changed).tolist() == [pos]
    np.testing.assert_array_equal(np.asarray(logits_over)[[0, 2]],
                                  np.asarray(logits_inside)[[0, 2]])
    assert np.all(np.isfinite(np.asarray(logits_over, np.float32)))


def _whole_updates(hlo: str, layer_elems: int):
    """``dynamic_update_slice`` ops in ``hlo`` whose update holds at least
    ``layer_elems`` elements: a whole layer's cache written back."""
    found = []
    for line in hlo.splitlines():
        if "dynamic_update_slice" not in line:
            continue
        m = re.search(r":\s*\(tensor<([0-9x]+)x\w+>,\s*tensor<([0-9x]+)x\w+>",
                      line)
        if m and np.prod([int(d) for d in m.group(2).split("x")]) >= \
                layer_elems:
            found.append(line.strip()[:200])
    return found


@pytest.mark.parametrize("form", ["dense", "int8", "paged"])
def test_decode_lowering_writes_no_whole_layer(form):
    """The lowered decode step (qwen2-7b smoke) writes no whole layer's
    cache back into the stack; the new token goes in by scatter."""
    cfg, model, _ = _model()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: _cache(form, cfg, model))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    hlo = jax.jit(
        lambda p, c, t: model.decode_step(RunConfig(), p, c, {"token": t}),
        donate_argnums=(1,)).lower(params, cache, tok).as_text()
    kv = cache.layers[0]["k"].shape
    layer_elems = int(np.prod(kv[1:]))
    assert _whole_updates(hlo, layer_elems) == []
    assert "stablehlo.scatter" in hlo


_SEQ_SHARD = textwrap.dedent("""
    import re
    import jax, jax.numpy as jnp, numpy as np
    from repro import configs
    from repro.launch.mesh import make_host_mesh
    from repro.models import RunConfig, build
    from repro.serving import Engine

    cfg = configs.smoke("qwen2-7b")
    model = build(cfg)
    mesh = make_host_mesh((1, 4), ("data", "model"))
    engine = Engine(model, RunConfig(), mesh=mesh, seq_shard=True)
    params = engine.shard_params(model.init(jax.random.PRNGKey(0)))
    cache = engine.new_cache(4, 64)
    tok = jnp.zeros((4, 1), jnp.int32)
    with engine._ctx():
        hlo = engine._jit_decode(cache).lower(params, cache, tok).as_text()
    g, b, s, kv, hd = cache.layers[0]["k"].shape
    block = b * (s // 4) * kv * hd  # one layer's block on one chip
    dus = []
    for line in hlo.splitlines():
        m = re.search(r"dynamic_update_slice.*:\\s*\\(tensor<[0-9x]+x\\w+>,"
                      r"\\s*tensor<([0-9x]+)x\\w+>", line)
        if m and np.prod([int(d) for d in m.group(1).split("x")]) >= block:
            dus.append(line.strip()[:160])
    local = f"tensor<{b}x{s // 4}x{kv}x{hd}xbf16>"
    selects = [l.strip()[:160] for l in hlo.splitlines()
               if "stablehlo.select" in l and l.rstrip().endswith(local)]
    print("SHARD_MAP", "sdy.manual_computation" in hlo
          or "shard_map" in hlo or "manual" in hlo)
    print("DUS", dus)
    print("SELECT", selects)
    print("SCATTER", "stablehlo.scatter" in hlo)
""")


def test_seq_shard_decode_lowering_writes_in_place():
    """Over a 1x4 mesh with the cache sequence-sharded, each chip scatters
    the rows whose position it holds into its block of the stack: no
    whole-block write-back, no whole-block select."""
    out = run_in_subprocess(_SEQ_SHARD, n_devices=4)
    lines = dict(l.split(" ", 1) for l in out.splitlines() if " " in l)
    assert lines["SHARD_MAP"] == "True"
    assert lines["DUS"] == "[]"
    assert lines["SELECT"] == "[]"
    assert lines["SCATTER"] == "True"
