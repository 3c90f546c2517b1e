"""The served decode path over a 1x4 mesh against the plain reference.

``Engine(mesh=1x4, seq_shard=True)`` under ``ContinuousBatcher`` with
fused sampling, the path the four-chip benchmark cell serves: weights
tensor-parallel over "model", the KV cache sequence-sharded over it,
each chip's block through the ``decode_attention_partials`` kernel
(Pallas, interpret mode here) and the blocks combined across chips.
Every logit row the served path samples from (each admission's last
prompt position, then every decode step of every row) is compared with
the float32 full forward pass of ``bench/models/decoder_lm.py`` on the
same weights, made from the same seed by ``bench/weights.py``.

The model is Qwen2-shaped and small: 2 layers, hidden 64, 8 query and
4 KV heads, vocab 512. The cache holds 256 positions, 64 on each chip,
the least block the kernel takes; prompts of 45-100 tokens with 20 new
tokens each put most rows across two chips' blocks.
"""
import json
import textwrap

import pytest

from conftest import REPO, run_in_subprocess

# The served path keeps weights, activations and the cache in bfloat16;
# the reference is float32 on the same (bf16-rounded) weights. Rounding
# the activations moves this model's logits (largest about 5) by up to
# 0.03 (0.0298 measured, kernel and jnp block alike). 0.1 leaves three
# times that; a decode that drops one chip's partials moves them by
# 0.9 or more.
TOL = 0.1

_CODE = textwrap.dedent("""
    import json, os, sys
    sys.path[:0] = [os.path.join(REPO, "bench")]
    import jax, numpy as np
    import harness, weights
    from repro.dist import collectives
    from repro.launch.mesh import make_host_mesh
    from repro.models import RunConfig, build
    from repro.obs import Observability, TraceRecorder
    from repro.serving import ContinuousBatcher, Engine, Request
    from repro.serving import engine as engine_mod

    SEED = 2**31 + 16
    MAX_LEN = 256
    conf = {**harness.load_json(os.path.join(
        REPO, "bench", "configs", "qwen2-7b-1x4.json")),
        "hidden_size": 64, "num_attention_heads": 8,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "num_hidden_layers": 2, "vocab_size": 512}
    ref_lm = harness.load_module(harness.HERE / "models" / "decoder_lm.py")
    mesh = make_host_mesh((1, 4), ("data", "model"))
    collectives.set_fused_partials(True)      # the kernel, as on the chip

    real_partials = collectives._partial_decode
    blocks = set()

    def partials(drop):
        def run(q, k, v, lengths, offset, window, cap):
            blocks.add(k.shape[1])
            num, den, m = real_partials(q, k, v, lengths, offset, window,
                                        cap)
            if drop is None:
                return num, den, m
            keep = (jax.lax.axis_index("model") != drop).astype(num.dtype)
            return num * keep, den * keep, m
        return run

    seen = []
    real_sample = engine_mod.fused_sample

    def spy(logits, key, **kw):
        jax.debug.callback(
            lambda x: seen.append(np.asarray(x, np.float32)), logits)
        return real_sample(logits, key, **kw)
    engine_mod.fused_sample = spy

    def serve(drop):
        collectives._partial_decode = partials(drop)
        seen.clear()
        eng = Engine(build(harness.program_config(conf)),
                     RunConfig(cache_pad=16), mesh=mesh, seq_shard=True)
        obs = Observability(tracer=TraceRecorder())
        eng.attach_obs(obs, lambda: 1.5)
        params = weights.program_params(ref_lm.layout(conf), SEED,
                                        eng.params_sharding)
        bat = ContinuousBatcher(eng, params, n_slots=4, max_len=MAX_LEN,
                                fused_sampling=True)
        rng = np.random.default_rng(0)
        reqs = [Request(i, rng.integers(0, 512, n).astype(np.int32), 20)
                for i, n in enumerate([70, 90, 45, 100, 60, 80])]
        for r in reqs:
            bat.submit(r)
        # which request and position each sampled logit row belongs to
        rows = []
        decode, prefill = eng.decode_sample, eng.prefill_into_sample

        def decode_sample(params, cache, token, key, **kw):
            n = np.asarray(cache.lengths)
            rows.append([(s, r.rid, int(n[s]))
                         for s, r in enumerate(bat.scheduler.slots)
                         if r is not None])
            return decode(params, cache, token, key, **kw)

        def prefill_sample(params, cache, row, tokens, key, **kw):
            rows.append([(0, bat.scheduler.slots[row].rid,
                          tokens.shape[1] - 1)])
            return prefill(params, cache, row, tokens, key, **kw)
        eng.decode_sample, eng.prefill_into_sample = (decode_sample,
                                                      prefill_sample)
        while not all(r.done for r in reqs):
            bat.step()
        jax.effects_barrier()
        kv = bat.cache.layers[0]["k"].sharding.spec
        assert kv[2] == "model", kv
        assert len(seen) == len(rows), (len(seen), len(rows))

        seqs = [np.concatenate([r.prompt, np.asarray(r.generated,
                                                     np.int32)])
                for r in reqs]
        toks = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
        for i, s in enumerate(seqs):
            toks[i, :len(s)] = s
        with jax.default_matmul_precision("highest"):
            x = ref_lm._hidden(conf, SEED, toks, False, 8)
            head = ref_lm._jitted(ref_lm._key(conf), False)[2]
            ref = np.asarray(head(ref_lm.base_key(SEED),
                                  x.reshape(-1, x.shape[-1])))
        ref = ref.reshape(toks.shape + (-1,))
        errs = [float(np.abs(lg[s] - ref[rid, pos]).max())
                for lg, where in zip(seen, rows) for s, rid, pos in where]
        gauges = {l.split()[0]: float(l.split()[1])
                  for l in obs.registry.render().splitlines()
                  if l.startswith("repro_engine_")}
        return {"n_rows": len(errs), "max_err": max(errs),
                "max_logit": float(np.abs(ref).max()),
                "decode_steps": bat.decode_steps, "gauges": gauges,
                "param_bytes": sum(l.nbytes for l in
                                   jax.tree.leaves(params)),
                "layout": [e for e in obs.tracer.events
                           if e["event"] == "layout"]}

    out = {"sound": serve(None), "one_shard_dropped": serve(1),
           "blocks": sorted(blocks)}
    print("RESULT " + json.dumps(out))
""").replace("REPO", repr(REPO))


@pytest.fixture(scope="module")
def served():
    out = run_in_subprocess(_CODE, n_devices=4, timeout=300)
    line = [l for l in out.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_mesh_decode_logits_match_the_reference(served):
    s = served["sound"]
    # 6 admissions plus every row of every decode step; at least 16
    # decode steps of each of the 6 rows
    assert s["n_rows"] == 6 * 20 and s["decode_steps"] >= 6 * 16
    # each chip's block of the 256-position cache went through the
    # partials, none whole (the unsharded fallback)
    assert served["blocks"] == [256 // 4]
    assert s["max_err"] <= TOL, s


def test_mesh_decode_without_one_shard_fails_the_tolerance(served):
    assert served["one_shard_dropped"]["max_err"] > TOL, served


def test_mesh_engine_reports_what_it_serves(served):
    """One ``layout`` event for the one serving cache, and the placement
    gauges: four devices, each with a quarter of the parameters give or
    take the replicated norm gains and biases."""
    s = served["sound"]
    (ev,) = s["layout"]
    assert ev["t"] == 1.5
    assert ev["mesh"] == {"data": 1, "model": 4}
    assert (ev["strategy"], ev["seq_shard"], ev["attn_impl"]) == \
        ("tp", True, "seq_shard")
    # (layers, batch, sequence, kv heads, head dim): the sequence sharded
    assert ev["kv_specs"] == [[None, None, "model", None, None]]
    per_dev = s["gauges"]["repro_engine_param_bytes_per_device"]
    assert ev["param_bytes_per_device"] == per_dev
    assert s["gauges"]["repro_engine_mesh_devices"] == 4
    assert s["param_bytes"] / 4 <= per_dev < s["param_bytes"] / 3
