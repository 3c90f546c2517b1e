"""The program's host spans on the profiler's clock (``repro.obs.span``).

A ``jax.profiler`` trace on the CPU around one tiny ``Engine.classify``
and a few ``ContinuousBatcher`` rounds must hold the ``repro:classify``,
``repro:round``, ``repro:prefill`` and ``repro:decode`` host events, one
per call, admission and decode dispatch, with every prefill and decode
nested inside a round.
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.models import RunConfig, build
from repro.serving import ContinuousBatcher, Engine, Request


@pytest.fixture(scope="module")
def engines():
    clf_cfg = configs.smoke("distilbert-imdb")
    clf = build(clf_cfg)
    lm = build(configs.smoke("qwen2-7b"))
    return ((Engine(clf, RunConfig()), clf.init(jax.random.PRNGKey(0)),
             clf_cfg.vocab_size),
            (Engine(lm, RunConfig(cache_pad=8)),
             lm.init(jax.random.PRNGKey(1))))


def _host_spans(log_dir):
    """(name, start_ns, end_ns) of every ``repro:`` host event."""
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                        if e.name.startswith("repro:")]
    return out


def _within(ev, outer):
    return any(s <= ev[1] and ev[2] <= e for _, s, e in outer)


def _requests():
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(1, 200, 5 + i)
                    .astype(np.int32), max_new_tokens=3)
            for i in range(3)]


@pytest.mark.parametrize("mode", [
    dict(fused_sampling=True),
    dict(fused_sampling=False),
    dict(paged=True, page_size=8),
    dict(batched=False),
], ids=["batched-fused", "batched-host-sampler", "paged", "per-slot"])
def test_profiler_trace_holds_nested_program_spans(engines, mode,
                                                   tmp_path):
    (clf_eng, clf_params, vocab), (lm_eng, lm_params) = engines
    tokens = np.random.default_rng(0).integers(
        0, vocab, (4, 16)).astype(np.int32)
    bat = ContinuousBatcher(lm_eng, lm_params, n_slots=2, max_len=24,
                            **mode)
    for r in _requests():
        bat.submit(r)
    clf_eng.classify(clf_params, tokens)      # compile outside the trace

    jax.profiler.start_trace(str(tmp_path))
    try:
        labels = clf_eng.classify(clf_params, tokens)
        bat.run()
    finally:
        jax.profiler.stop_trace()
    assert labels.shape == (4,)
    assert len(bat.scheduler.completed) == 3

    ev = _host_spans(str(tmp_path))
    by = {n: [e for e in ev if e[0] == "repro:" + n]
          for n in ("classify", "round", "prefill", "decode")}
    assert len(by["classify"]) == 1
    assert len(by["round"]) == bat.rounds
    assert len(by["prefill"]) == 3                    # one per admission
    assert len(by["decode"]) == bat.decode_dispatches
    for name in ("prefill", "decode"):
        assert all(_within(e, by["round"]) for e in by[name]), name
    # the classify call and the rounds are apart
    assert not any(_within(e, by["classify"]) for e in by["round"])
    buckets = bat.take_bucket_s()
    assert buckets["prefill"] > 0 and buckets["decode_attention"] > 0
