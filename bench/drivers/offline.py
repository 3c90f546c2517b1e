"""The paper's offline job, back to back, for the measured window.

Set-up: the benchmark's weights go into the program's ``ArtifactStore``
as ``models/clf``; the dataset is made from the seed; one ``classify`` at
the chunk shape compiles (or loads) the one executable the job uses.

Window: jobs of ``n_items`` reviews, each decomposed into chunks of
``chunk_items`` and run by a fresh ``Orchestrator`` over fresh
``ServerlessFunction``s (so cold loads recur, as on a serverless
platform): ``Orchestrator.run`` -> ``ServerlessFunction.invoke`` ->
``Engine.classify``. At the window's end the next ``classify`` raises
and the job in flight stops. ``items_per_s`` counts the reviews whose
chunk was committed inside the window.

Check: every chunk is committed at most once and every finished job
covers its dataset exactly; for a sample of committed reviews drawn from
the seed, the committed label is the argmax of the logits the timed
classify computed, and those logits lie within ``logit_err`` of the
float32 reference (the widest gap over the sample's logits, over the
largest reference logit). The mean gap is logged beside it.
"""
from __future__ import annotations

import gc
import pickle
import time
from typing import Dict, List

import numpy as np

import gen
import harness
import weights
from harness import Check, Record, annotate

RESULT = "/result/"


class WindowClosed(Exception):
    """Raised by the first classify past the window's end."""


def logit_err(logits: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap between two sets of logits, over the largest reference
    logit."""
    return float(np.max(np.abs(logits - ref)) / np.max(np.abs(ref)))


def logit_err_mean(logits: np.ndarray, ref: np.ndarray) -> float:
    """Mean gap over the mean absolute reference logit: steadier from
    seed to seed than the widest gap, logged beside it."""
    return float(np.mean(np.abs(logits - ref)) / np.mean(np.abs(ref)))


def run(ctx: harness.Context) -> Record:
    from repro.core import (ArtifactStore, BatchJob, LatencyModel,
                            Orchestrator, OrchestratorConfig,
                            ServerlessFunction, decompose, merge)
    from repro.data.pipeline import DatasetRef
    from repro.models import RunConfig, build
    from repro.serving import Engine

    c, t = ctx.cell.config, ctx.cell.traffic
    n, seq, chunk = t["n_items"], t["seq_len"], t["chunk_items"]
    spans: List = []
    logits: Dict = {}          # (job, chunk) -> logits of its classify
    commits: List = []         # (key, t, accepted)
    state = {"deadline": float("inf"), "current": None}

    class TimedEngine(Engine):
        def classify(self, params, tokens):
            if time.perf_counter() >= state["deadline"]:
                raise WindowClosed
            t0 = time.perf_counter()
            with annotate("classify"):
                out = super().classify(params, tokens)
            spans.append(("classify", t0, time.perf_counter()))
            return out

        def classify_logits(self, params, tokens):
            out = super().classify_logits(params, tokens)
            logits[state["current"]] = np.asarray(out, np.float32)
            return out

    class TimedStore(ArtifactStore):
        def put(self, key, blob, *, overwrite=True):
            with annotate("store_put"):
                ok = super().put(key, blob, overwrite=overwrite)
            if RESULT in key:
                commits.append((key, time.perf_counter(), ok))
            return ok

    class Function(ServerlessFunction):
        def invoke(self, job, ch, data=None):
            state["current"] = (job.job_id, ch.chunk_id)
            t0 = time.perf_counter()
            with annotate("invoke"):
                out = super().invoke(job, ch, data)
            spans.append(("invoke", t0, time.perf_counter()))
            return out

    model = build(ctx.program_cfg)
    layout = ctx.model.layout(c)
    weights.check_layout(layout, weights.flat_paths(model.param_specs))
    engine = TimedEngine(model, RunConfig())
    params = weights.program_params(layout, ctx.seed)
    store = TimedStore()
    store.put_tree("models/clf", params)
    tokens, _ = gen.imdb_reviews(n, seq, c["vocab_size"],
                                 gen.key32(ctx.seed, "imdb"))
    engine.classify(params, tokens[:chunk])              # compile / load
    del params
    lat = LatencyModel(per_item_s=None)
    cfg = OrchestratorConfig(max_concurrency=t["max_concurrency"])
    data = {"tokens": tokens}
    setup_s = ctx.setup_s()

    prof = harness.Profile(ctx)
    prof.start()
    finished, chunks_of = [], {}
    built = engine.compile_count
    t0 = time.perf_counter()
    state["deadline"] = t0 + ctx.seconds
    with annotate("window"):
        k = 0
        while time.perf_counter() < state["deadline"]:
            job = BatchJob(f"job{k}", DatasetRef("imdb", n, seq,
                                                 c["vocab_size"]),
                           "models/clf", chunk)
            chunks_of[job.job_id] = (job, decompose(job))
            try:
                Orchestrator(store, cfg).run(
                    job, chunks_of[job.job_id][1],
                    lambda i: Function(i, store, lat, engine=engine,
                                       params_ref="models/clf"), data=data)
            except WindowClosed:
                break
            with annotate("merge"):
                try:
                    merge(store, job, chunks_of[job.job_id][1])
                except AssertionError as e:      # counted as lost below
                    ctx.log(f"{job.job_id}: merge failed: {e}")
            finished.append(job.job_id)
            k += 1
    t1 = time.perf_counter()
    prof.stop()
    window_s = t1 - t0
    built = engine.compile_count - built
    memory = harness.memory_peak(ctx.devices)

    sizes = {}
    for job, chunks in chunks_of.values():
        for ch in chunks:
            sizes[f"job/{job.job_id}/result/{ch.chunk_id}"] = (
                job.job_id, ch)
    in_window = [key for key, tc, ok in commits
                 if ok and t0 <= tc < state["deadline"]]
    items = sum(sizes[key][1].n_items for key in in_window)
    accepted: Dict[str, int] = {}
    for key, _, ok in commits:
        accepted[key] = accepted.get(key, 0) + int(ok)
    dups = sum(v > 1 for v in accepted.values())
    lost = 0
    committed = {}
    for key, cnt in accepted.items():
        if cnt:
            committed[key] = np.asarray(
                pickle.loads(store.get(key))["predictions"])
    for jid in finished:
        job, chunks = chunks_of[jid]
        lost += sum(len(committed.get(f"job/{jid}/result/{ch.chunk_id}",
                                      ())) != ch.n_items for ch in chunks)

    # the sample: committed reviews of the window, drawn from the seed
    pool = [(key, r) for key in in_window
            for r in range(sizes[key][1].n_items)]
    r = gen.rng(ctx.seed, "check")
    pick = [pool[i] for i in r.choice(len(pool), min(t["check_rows"],
                                                     len(pool)),
                                      replace=False)] if pool else []
    rows, prog = [], []
    mismatch = 0
    for key, i in pick:
        jid, ch = sizes[key]
        preds = committed[key]
        lg = logits.get((jid, ch.chunk_id))
        if len(preds) != ch.n_items or lg is None or len(lg) != ch.n_items:
            mismatch += 1
            continue
        rows.append(ch.start + i)
        prog.append(lg[i])
        mismatch += int(preds[i] != int(np.argmax(lg[i])))

    # free the program's state before the reference runs
    del engine, store, logits, committed, data
    gc.collect()
    err = err_mean = float("nan")
    if rows:
        ref = ctx.model.logits(c, ctx.seed, tokens[rows])
        err = logit_err(np.stack(prog), ref)
        err_mean = logit_err_mean(np.stack(prog), ref)
        ctx.log(f"logit gap: widest {err!r}, mean {err_mean!r}")
    checks = [Check("no_sample", float(not pick), 0.0),
              Check("dup_commits", float(dups), 0.0),
              Check("lost_chunks", float(lost), 0.0),
              Check("label_vs_logits", float(mismatch), 0.0),
              Check("logit_err", err, t["limits"]["logit_err"])]
    invokes = [s for s in spans if s[0] == "invoke"]
    ctx.log(f"window {window_s:.3f} s: {len(finished)} jobs finished, "
            f"{len(in_window)} chunks committed, {len(invokes)} invokes, "
            f"{built} executables built; set-up {setup_s:.3f} s")
    return Record(
        end_to_end={"items_per_s": items / ctx.seconds, "setup_s": setup_s},
        checks=checks, attempted=items, failed=mismatch + lost,
        memory_peak_bytes=memory,
        spans=[s for s in spans if s[1] >= t0], trace=prof.trace,
        facts={"t0": t0, "t1": t1, "logit_err": err,
               "logit_err_mean": err_mean,
               "seq_len": seq,
               "check_tokens": tokens[rows],
               "classify_calls": sum(1 for s in spans
                                     if s[0] == "classify" and s[1] >= t0),
               "chunk_items": chunk})
