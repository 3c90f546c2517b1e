"""Open-loop streaming traffic against the program's HTTP front door.

The window drives ``repro.launch.serve.run_http`` as its command line
would (``HttpFrontDoor`` -> ``EventRouter`` -> ``ReplicaPool`` ->
``ContinuousBatcher`` -> ``Engine``) with the mix's ``server`` flags; the
only change is that the engine's weights are the benchmark's, made from
the seed on the device.

The clients are ``bench/loadgen.py``, a process of its own that imports
no JAX: they share no interpreter lock or event loop with the server.

Set-up: weights, then one request per prompt length (each compiles, or
loads, its prefill and the decode step), then ``preroll_s`` of the same
traffic so the window starts with the server under its steady load.

Window: requests arrive on the mix's schedule (``bench/gen.py``) for
``--seconds``; the pre-roll and the window are drawn apart, so every
seed puts the same gaps and lengths in the window. Every request is
timed from when it was due. With ``--trace 1`` the profiler records the
window's last ``trace_s`` seconds (the mix's, or all of it). Then the
clients wait until every request due in the window has its first token,
and disconnect the rest.

* ``ttft_p95_ms``: due -> first token at the client, requests due in the
  window (a request that never gets one counts as infinitely late);
  reported per layer (``bench/metrics/ttft_p95_ms.online.py``), since a
  stall of the host adds to it whole;
* ``itl_p95_ms``: gaps between successive tokens of one stream, where
  the later token arrived in the window;
* ``tokens_per_s``: tokens that arrived in the window, over the window.

Check: a sample of finished requests drawn from the seed, with the
longest among them, goes through the float32 reference (prompt followed
by the served tokens); every served token must lie within ``token_gap``
of the reference's best logit at its position. All traffic is greedy.
"""
from __future__ import annotations

import asyncio
import contextlib
import gc
import json
import os
import sys
import time
from typing import Dict, List, Optional
from unittest import mock

import numpy as np

import gen
import harness
import weights
from harness import Check, Record, annotate

LOADGEN = harness.HERE / "loadgen.py"
DRAIN_S = 60.0


class Req:
    """One request as its client saw it (``time.monotonic`` seconds)."""

    def __init__(self, i: int, prompt: np.ndarray, n_out: int, seen: dict):
        self.i, self.prompt, self.n_out = i, prompt, n_out
        self.due: float = seen["due"]
        self.sent: Optional[float] = seen["sent"]
        self.times: List[float] = seen["times"]
        self.tokens: List[int] = seen["tokens"]
        self.done: bool = seen["done"]
        self.error: Optional[str] = seen["error"]


def schedule(t: dict, vocab: int, seed: int, seconds: float):
    """(due offsets from the pre-roll's start, prompts, output lengths).
    The pre-roll and the window are drawn apart, each from its own
    salt, so the window holds the same gaps and lengths for every seed."""
    at, pairs = [], []
    for span, start, length in (("preroll", 0.0, t["preroll_s"]),
                                ("window", t["preroll_s"], seconds)):
        k = max(int(round(length / t["block_s"])), 1) if "block_s" in t \
            else 1
        a = gen.stratified_arrivals(t["rate_rps"], length, seed, span, k)
        at.append(start + a)
        pairs += gen.length_pairs(t, len(a), seed, span, k)
    prompts = [gen.prompt_tokens(p, vocab, seed, i)
               for i, (p, _) in enumerate(pairs)]
    return np.concatenate(at), prompts, [o for _, o in pairs]


def run(ctx: harness.Context) -> Record:
    from repro.launch import serve
    from repro.models import build
    from repro.obs.trace import load_jsonl
    from repro.serving import Engine

    c, t = ctx.cell.config, ctx.cell.traffic
    vocab = c["vocab_size"]
    layout = ctx.model.layout(c)
    weights.check_layout(layout, weights.flat_paths(
        build(ctx.program_cfg).param_specs))

    engines: List[Engine] = []

    class BenchEngine(Engine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            engines.append(self)

        def init_params(self, seed):
            return weights.program_params(layout, ctx.seed,
                                          self.params_sharding)

    at, prompts, n_out = schedule(t, vocab, ctx.seed, ctx.seconds)
    p_lens = sorted(set(t["prompt_len"]["values"]))
    trace_path = os.path.join(ctx.tmp, "requests.jsonl")
    argv = ["--http", "--port", "0", "--seed", "0",
            "--prompt-len", str(max(p_lens)),
            "--max-new-tokens", str(max(t["output_len"]["values"])),
            *t["server"]] + (["--trace", trace_path] if ctx.trace else [])
    args = serve.build_parser().parse_args(argv)
    prof = harness.Profile(ctx)
    st: Dict = {}

    async def clients(door):
        plan = {"port": door.port, "drain_s": DRAIN_S,
                "w0": t["preroll_s"], "w1": t["preroll_s"] + ctx.seconds,
                # set-up: each prompt shape once, two tokens (prefill,
                # decode)
                "warm": [{"prompt": gen.prompt_tokens(
                    p, vocab, ctx.seed, -1 - k).tolist(), "n_out": 2}
                    for k, p in enumerate(p_lens)],
                "requests": [{"due": float(a), "prompt": p.tolist(),
                              "n_out": int(o)}
                             for a, p, o in zip(at, prompts, n_out)]}
        plan_path = os.path.join(ctx.tmp, "plan.json")
        seen_path = os.path.join(ctx.tmp, "seen.json")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        proc = await asyncio.create_subprocess_exec(
            sys.executable, str(LOADGEN), plan_path, seen_path,
            stdout=asyncio.subprocess.PIPE)
        try:
            line = await proc.stdout.readline()
            if not line:
                raise RuntimeError(f"the load generator ended before the "
                                   f"window (exit {await proc.wait()})")
            hello = json.loads(line)
            st["warm_sent"] = hello["warm_sent"]
            st["w0"] = w0 = hello["base"] + t["preroll_s"]
            st["w1"] = w1 = w0 + ctx.seconds
            await asyncio.sleep(max(w0 - time.monotonic(), 0.0))
            st["setup_s"] = ctx.setup_s()
            built = sum(e.compile_count for e in engines)
            # a traced run traces the window's last ``trace_s`` seconds:
            # the decode step's device ops are too many to trace a whole
            # window
            st["traced"] = (max(w1 - t.get("trace_s", ctx.seconds), w0), w1)
            await asyncio.sleep(max(st["traced"][0] - time.monotonic(), 0.0))
            prof.start()
            with annotate("window"):
                await asyncio.sleep(max(w1 - time.monotonic(), 0.0))
            prof.stop()
            st["built"] = sum(e.compile_count for e in engines) - built
            rc = await proc.wait()
            if rc != 0:
                raise RuntimeError(f"the load generator failed (exit {rc})")
        finally:
            if proc.returncode is None:
                proc.kill()
                await proc.wait()
        with open(seen_path) as f:
            seen = json.load(f)["requests"]
        return [Req(i, p, o, x) for i, (p, o, x) in
                enumerate(zip(prompts, n_out, seen))]

    with mock.patch.object(serve, "Engine", BenchEngine), \
            contextlib.redirect_stdout(sys.stderr):
        out = serve.run_http(args, serve.mesh_from_args(args),
                             ctx.program_cfg, until=clients)
    reqs: List[Req] = out["clients"]
    memory = harness.memory_peak(ctx.devices)
    del out
    gc.collect()
    w0, w1 = st["w0"], st["w1"]

    window = [r for r in reqs if w0 <= r.due < w1]
    ttft = [(r.times[0] - r.due) * 1e3 if r.times else float("inf")
            for r in window]
    gaps, n_tok = [], 0
    for r in reqs:
        for a, b in zip(r.times, r.times[1:]):
            if w0 <= b < w1:
                gaps.append((b - a) * 1e3)
        n_tok += sum(w0 <= x < w1 for x in r.times)
    late = sorted(r.sent - r.due for r in window if r.sent is not None)
    failed = [r for r in window if r.error is not None or not r.times]
    ctx.log(f"window {w1 - w0:.3f} s: {len(window)} requests due, "
            f"{len(failed)} failed, {n_tok} tokens; generator late p50 "
            f"{1e3 * late[len(late) // 2] if late else 0:.2f} ms, max "
            f"{1e3 * late[-1] if late else 0:.2f} ms; {st['built']} "
            f"executables built in the window; set-up {st['setup_s']:.3f} s")

    # the check: finished requests, the longest first, then a sample
    fin = [r for r in reqs if r.done]
    short = sum(len(r.tokens) != r.n_out or not all(
        0 <= x < vocab for x in r.tokens) for r in fin)
    pick: List[Req] = []
    if fin:
        longest = max(fin, key=lambda r: (len(r.tokens), -r.i))
        rest = [r for r in fin if r is not longest]
        k = min(t["check_requests"] - 1, len(rest))
        g = gen.rng(ctx.seed, "check")
        pick = [longest] + [rest[i] for i in sorted(
            g.choice(len(rest), k, replace=False))]
    token_gap = token_gap_mean = float("nan")
    seqs = [np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
            for r in pick]
    if pick:
        gaps_ref = ctx.model.served_gaps(c, ctx.seed, seqs,
                                         [len(r.prompt) for r in pick])
        token_gap = float(max(float(np.max(x)) for x in gaps_ref))
        token_gap_mean = float(np.mean(np.concatenate(gaps_ref)))
    lim = t["limits"]
    checks = [Check("no_sample", float(not pick), 0.0),
              Check("short_streams", float(short), 0.0),
              Check("token_gap", token_gap, lim["token_gap"]),
              Check("token_gap_mean", token_gap_mean,
                    lim["token_gap_mean"])]
    ctx.log(f"checked {len(pick)} requests, "
            f"{sum(len(r.tokens) for r in pick)} served tokens")

    events = load_jsonl(trace_path) if ctx.trace else []
    offset = 0.0
    q0 = [e for e in events if e["event"] == "queued"]
    if q0:
        offset = st["warm_sent"] - q0[0]["t"]
    return Record(
        end_to_end={"ttft_p95_ms": gen.percentile(ttft, 95),
                    "itl_p95_ms": gen.percentile(gaps, 95),
                    "tokens_per_s": n_tok / ctx.seconds,
                    "setup_s": st["setup_s"]},
        checks=checks, attempted=len(window), failed=len(failed),
        memory_peak_bytes=memory,
        program_events=events, trace=prof.trace,
        facts={"w0": w0, "w1": w1, "traced": st["traced"],
               "server_offset": offset,
               "n_slots": args.n_slots, "check_seqs": seqs,
               "ttft_by_due": [(r.due - w0, x) for r, x in zip(window, ttft)],
               "check_prompts": [len(r.prompt) for r in pick],
               "streams": [(r.times[0], r.times[-1]) for r in reqs
                           if r.times],
               "tokens": [(x, len(r.prompt), j) for r in reqs
                          for j, x in enumerate(r.times) if w0 <= x < w1]})
