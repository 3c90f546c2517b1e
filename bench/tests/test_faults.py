"""A run whose timed path is broken comes out not correct.

Each test drives a whole run on the CPU at a small size, past the
harness's look for a chip, with one fault planted in the program where
the answer is produced: the check must catch it. A sound run at the
same size comes out correct.
"""
import json
import time

import numpy as np
import pytest

import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
SEED = 2**31 + 4242
ENC = {"dim": 64, "n_heads": 4, "hidden_dim": 128, "n_layers": 2,
       "vocab_size": 512, "max_position_embeddings": 64}
DEC = {"hidden_size": 64, "num_attention_heads": 4,
       "num_key_value_heads": 2, "intermediate_size": 128,
       "num_hidden_layers": 2, "vocab_size": 512}
OFFLINE = {"n_items": 96, "seq_len": 32, "chunk_items": 8,
           "check_rows": 32}
ONLINE = {"rate_rps": 4.0, "preroll_s": 1.0,
          "prompt_len": {"values": [8, 16], "shares": [0.5, 0.5]},
          "output_len": {"values": [4, 8], "shares": [0.5, 0.5]},
          "server": ["--n-slots", "4", "--fused-sampling",
                     "--max-replicas", "1", "--cold-start", "0"]}


def _run(workload, config, traffic, seconds=1.5):
    import jax
    return harness.run_cell(BENCH, workload, seed=SEED, seconds=seconds,
                            trace=False, t_start=time.perf_counter(),
                            devices=jax.devices(), config_override=config,
                            traffic_override=traffic)


def _offline():
    return _run("distilbert-imdb.offline-512", ENC, OFFLINE)


def _online(**traffic):
    return _run("qwen2-7b-8L.chat-steady", DEC, {**ONLINE, **traffic},
                seconds=3.0)


def test_sound_offline_run_is_correct():
    out = _offline()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0


def test_offline_answer_altered(monkeypatch):
    from repro.serving import Engine
    real = Engine.classify
    monkeypatch.setattr(Engine, "classify",
                        lambda self, p, t: 1 - real(self, p, t))
    out = _offline()
    assert not out["correct"]
    assert out["checks"]["label_vs_logits"]["value"] > 0


def test_offline_half_of_the_batch_left_out(monkeypatch):
    from repro.serving import Engine
    real = Engine.classify
    monkeypatch.setattr(Engine, "classify",
                        lambda self, p, t: real(self, p, t[:len(t) // 2]))
    out = _offline()
    assert not out["correct"]
    assert out["checks"]["lost_chunks"]["value"] > 0


def test_offline_logits_perturbed(monkeypatch):
    """Logits off by a few percent with the labels kept: only the
    comparison with the reference can see it."""
    from repro.serving import Engine
    real = Engine.classify_logits

    def off(self, p, t):
        out = real(self, p, t)
        return out + 0.1 * np.abs(out).max()
    monkeypatch.setattr(Engine, "classify_logits", off)
    out = _offline()
    assert not out["correct"]
    assert out["checks"]["logit_err"]["value"] > \
        out["checks"]["logit_err"]["limit"]


def test_sound_online_run_is_correct():
    out = _online()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_online_token_altered(monkeypatch):
    from repro.serving import Engine
    real = Engine.decode_sample

    def wrong(self, params, cache, token, key, **kw):
        toks, cache = real(self, params, cache, token, key, **kw)
        return (toks + 1) % self.model.cfg.vocab_size, cache
    monkeypatch.setattr(Engine, "decode_sample", wrong)
    out = _online()
    assert not out["correct"]
    assert out["checks"]["token_gap"]["value"] > \
        out["checks"]["token_gap"]["limit"]


@pytest.mark.parametrize("workload", ["distilbert-imdb.offline-512",
                                      "qwen2-7b-8L.chat-steady"])
def test_no_result_without_a_tpu(workload, capsys):
    """bench/run.py refuses the CPU: exit 2, nothing on stdout."""
    import subprocess
    import sys
    p = subprocess.run([sys.executable, str(harness.HERE / "run.py"),
                        "--workload", workload, "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, timeout=300,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                            "HOME": "/tmp"})
    assert p.returncode == 2
    assert p.stdout == ""


def test_online_half_of_the_batch_left_out(monkeypatch):
    """The decode step leaves half of its rows (every other one) out:
    they get back the token they fed in."""
    import jax.numpy as jnp
    from repro.serving import Engine
    real = Engine.decode_sample

    def half(self, params, cache, token, key, **kw):
        toks, cache = real(self, params, cache, token, key, **kw)
        fed = jnp.reshape(jnp.asarray(token), (-1,)).astype(toks.dtype)
        return toks.at[1::2].set(fed[1::2]), cache
    monkeypatch.setattr(Engine, "decode_sample", half)
    # longer streams, more often, so that every slot is in use
    out = _online(rate_rps=20.0, output_len={"values": [32, 64],
                                             "shares": [0.5, 0.5]})
    assert not out["correct"]
    assert out["checks"]["token_gap"]["value"] > \
        out["checks"]["token_gap"]["limit"]


def test_online_decode_state_left_unchanged(monkeypatch):
    """The decode step hands back the cache it was given (its key and
    value writes dropped) with the lengths moved on."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.serving import Engine
    real = Engine.decode_sample

    def stale(self, params, cache, token, key, **kw):
        old = jax.tree.map(jnp.copy, cache.layers)
        toks, new = real(self, params, cache, token, key, **kw)
        return toks, dataclasses.replace(new, layers=old)
    monkeypatch.setattr(Engine, "decode_sample", stale)
    out = _online()
    assert not out["correct"]
    assert out["checks"]["token_gap"]["value"] > \
        out["checks"]["token_gap"]["limit"]
