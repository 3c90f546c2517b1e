"""Bring-up check: the system's main paths on a TPU, at published widths.

    python chip_smoke.py              # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4    # one four-chip host: phase (d) only

(a) The paper's offline job: ``distilbert-imdb`` at its published size
    classifies 512 seeded reviews of 512 tokens through the offline path
    of ``repro.launch.serve`` (``MonolithicRunner`` and ``Orchestrator``
    → ``ServerlessFunction`` → ``Engine.classify``). Monolithic and
    parallel predictions must be identical, and the chip's logits on 8
    rows must agree with the same params run in float32 on the host CPU.
(b) The server: ``qwen2-7b`` cut to 8 of its 28 layers, every width as
    published, behind ``HttpFrontDoor`` on the wall clock with fused
    sampling; 8 concurrent in-process streaming clients must each get
    the requested number of tokens.
(c) The kernels on silicon: each attention kernel against its jnp
    reference at qwen2-7b attention widths over ragged lengths; then the
    same cut served through ``Engine`` → ``ContinuousBatcher`` with
    ``attn_impl="xla"``, ``"pallas"`` and paged ``"pallas"`` over an
    int8 KV cache, plus the fused sampling kernel, which must draw the
    host sampler's tokens at one seed. Reports greedy-token agreement
    and the largest first-step logit difference.
(d) Four chips: the full 28-layer ``qwen2-7b`` served over HTTP by
    ``run_http`` with ``--mesh 1x4 --seq-shard`` (a sequence-sharded KV
    cache); the 8-layer cut meshless on one chip is compared with the
    same cut on the mesh; the cut then serves over HTTP with ``--mesh
    1x4 --mesh-slices 4``, one replica per chip.

Weights are random, made from ``--seed`` on the device(s). Each phase
prints one line: config, layers, parameter bytes, compile seconds,
``peak_bytes_in_use`` per device (the process's peak so far) and what it
served. Any failed check raises, so the script exits non-zero; without
a TPU it exits non-zero before doing anything. Its last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0
CUT_LAYERS = 8            # of qwen2-7b's 28; see cut_config
PROMPT, NEW = 128, 32     # decoder prompt and generated tokens per request

_compile_s = [0.0]


def _count_compile(event: str, duration: float, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


def cut_config(layers: int = CUT_LAYERS):
    """qwen2-7b at every published width, ``layers`` of its 28 deep.

    One v5e chip has 16 GB. The full model is 7.6 B params (15.2 GB in
    bf16), which leaves no room for a cache; 8 layers with the full
    embedding and head are 2.95 B (5.9 GB)."""
    from repro import configs
    full = configs.get("qwen2-7b")
    return dataclasses.replace(full, n_layers=layers,
                               name=f"qwen2-7b-{layers}of{full.n_layers}L")


def _peak(device) -> int:
    """The process's peak bytes in use on ``device`` so far."""
    return (device.memory_stats() or {}).get("peak_bytes_in_use", 0)


def _peaks(devices) -> str:
    return "[" + ", ".join(f"{_peak(d) / 1e9:.2f}" for d in devices) + "] GB"


def _report(phase: str, cfg, t0: float, c0: float, devices, served: str):
    from repro.models import build
    from repro.models.common import param_bytes
    print(f"[{phase}] config={cfg.name} layers={cfg.n_layers} "
          f"param_bytes={param_bytes(build(cfg).param_specs) / 1e9:.2f}GB "
          f"compile_s={_compile_s[0] - c0:.1f} "
          f"wall_s={time.perf_counter() - t0:.1f} "
          f"peak_bytes_in_use={_peaks(devices)} | {served}", flush=True)


def _check(ok: bool, what: str):
    if not ok:
        raise AssertionError(what)


def _prompts(n: int, vocab: int, seed: int = SEED, length=None):
    rng = np.random.default_rng(seed)
    return rng.integers(1, vocab, size=(n, length or PROMPT),
                        dtype=np.int32)


# ---------------------------------------------------------------------------
# (a) the paper's offline job
# ---------------------------------------------------------------------------


def phase_offline(cfg, devices, *, n_items=512, seq_len=512, batch=32,
                  concurrency=8, n_check=8):
    from repro.data import imdb_reviews
    from repro.launch import serve
    from repro.models import RunConfig, build
    from repro.serving import Engine

    t0, c0 = time.perf_counter(), _compile_s[0]
    args = serve.build_parser().parse_args([
        "--arch", cfg.name, "--n-items", str(n_items), "--seq-len",
        str(seq_len), "--batch-size", str(batch), "--concurrency",
        str(concurrency), "--seed", str(SEED)])
    out = serve.run_offline(args, None, cfg)
    same = bool((out["mono_preds"] == out["par_preds"]).all())
    _check(same, "monolithic and parallel predictions differ")

    # the same params (same seed) on the chip vs float32 on the host CPU
    model = build(cfg)
    engine = Engine(model, RunConfig())
    params = engine.init_params(SEED)
    tokens = imdb_reviews(n=n_items, seq_len=seq_len, vocab=cfg.vocab_size,
                          seed=SEED)[0][:batch]
    chip = engine.classify_logits(params, tokens)[:n_check]
    cpu = jax.devices("cpu")[0]
    p32 = jax.device_put(jax.tree.map(
        lambda x: np.asarray(x, np.float32), jax.device_get(params)), cpu)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(
            lambda p, t: model.forward(RunConfig(), p, {"tokens": t})[0])(
                p32, jax.device_put(tokens[:n_check], cpu)))
    err = float(np.max(np.abs(chip - ref)))
    # bf16 weights and activations against fp32, six layers deep: about
    # 0.7% of the logit range on a v5e; allow 2%
    tol = 0.02 * float(np.max(np.abs(ref)))
    _check(bool(np.isfinite(chip).all()) and err <= tol,
           f"chip logits differ from the fp32 CPU reference by {err} "
           f"(tolerance {tol})")
    _check(bool((chip.argmax(-1) == out["par_preds"][:n_check]).all()),
           "served predictions differ from the chip's own logits")
    _report("a offline", cfg, t0, c0, devices,
            f"{n_items} items x {seq_len} tokens, batch {batch}, "
            f"concurrency {concurrency}: mono==parallel {same}; "
            f"logits vs fp32 CPU on {n_check} rows max|d|={err:.4f} "
            f"(tol {tol:.4f}, max|ref|={np.max(np.abs(ref)):.3f})")


# ---------------------------------------------------------------------------
# (b) the server
# ---------------------------------------------------------------------------


async def _stream(port: int, prompt, n_new: int):
    """One in-process streaming client; returns the NDJSON chunks."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "max_new_tokens": n_new})
    writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: smoke\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n{body}").encode())
    await writer.drain()
    status = await reader.readline()
    _check(b"200" in status, f"front door answered {status!r}")
    while (await reader.readline()) not in (b"\r\n", b"\n", b""):
        pass
    chunks = []
    while True:
        size = int((await reader.readline()).strip() or b"0", 16)
        if size == 0:
            break
        chunks.append(json.loads(await reader.readexactly(size)))
        await reader.readexactly(2)
    writer.close()
    return chunks


def _serve_http(cfg, flags, prompts):
    """Serve ``prompts`` to concurrent in-process streaming clients
    through ``repro.launch.serve.run_http`` with CLI ``flags`` (the mesh,
    if any, built from them as ``serve.main`` would). Every stream must
    end with ``NEW`` in-vocab tokens. Returns (run_http's output, the
    (n, NEW) tokens)."""
    from repro.launch import serve

    args = serve.build_parser().parse_args([
        "--http", "--port", "0", "--seed", str(SEED), "--cold-start", "0",
        "--prompt-len", str(prompts.shape[1]), "--max-new-tokens", str(NEW),
        *flags])

    async def clients(door):
        return await asyncio.gather(*(_stream(door.port, p, NEW)
                                      for p in prompts))

    out = serve.run_http(args, serve.mesh_from_args(args), cfg,
                         until=clients)
    tokens = []
    for chunks in out["clients"]:
        toks = [c["token"] for c in chunks if "token" in c]
        end = chunks[-1]
        _check(end.get("event") == "end" and end["done"]
               and end["n_tokens"] == NEW and len(toks) == NEW
               and all(0 <= t < cfg.vocab_size for t in toks),
               f"a stream ended short: {end}")
        tokens.append(toks)
    return out, np.array(tokens)


def phase_server(cfg, devices, *, n_clients=8):
    t0, c0 = time.perf_counter(), _compile_s[0]
    out, _ = _serve_http(cfg, ["--fused-sampling", "--n-slots",
                               str(n_clients), "--max-replicas", "1"],
                         _prompts(n_clients, cfg.vocab_size))
    rep = out["report"]
    _report("b server", cfg, t0, c0, devices,
            f"{len(out['clients'])}/{n_clients} concurrent streams x {NEW} "
            f"tokens (prompt {PROMPT}) over HTTP, fused sampling, wall "
            f"clock: ttft_p50={rep['ttft_p50_s']}s "
            f"tpot_p50={rep['tpot_p50_s']}s "
            f"tokens/s={rep['tokens_per_s']}")


# ---------------------------------------------------------------------------
# (c) the kernels on silicon
# ---------------------------------------------------------------------------


def _first_step_logits(engine, params, prompts, max_len, tok=None):
    """Prefill logits, then the logits of one decode step fed ``tok``
    (default: the prefill's greedy token). Returns (l0, l1, tok)."""
    logits0, cache = engine.prefill(params, prompts, max_len=max_len)
    if tok is None:
        tok = jnp.argmax(logits0, axis=-1).astype(jnp.int32)[:, None]
    logits1, _ = engine.decode(params, cache, tok)
    return np.asarray(logits0), np.asarray(logits1), tok


def _paged_first_step_logits(engine, params, prompts, tok, page_size):
    """The same two steps through a paged cache: per-row page install
    and chunked prefill (``extend_row``), then one paged decode step."""
    b, s = prompts.shape
    max_pages = s // page_size + 1
    cache = engine.new_paged_cache(b, b * max_pages + 1, page_size,
                                   max_pages)
    logits0 = []
    for r in range(b):
        pages = list(range(1 + r * max_pages, 1 + (r + 1) * max_pages))
        cache = engine.assign_row_pages(cache, r, pages, 0)
        row_logits, cache = engine.extend_row(params, cache, r,
                                              prompts[r:r + 1])
        logits0.append(np.asarray(row_logits)[0])
    logits1, _ = engine.decode(params, cache, tok)
    return np.stack(logits0), np.asarray(logits1)


def _streams(engine, params, prompts, max_len, **kw):
    from repro.serving import ContinuousBatcher, Request
    batcher = ContinuousBatcher(engine, params, n_slots=len(prompts),
                                max_len=max_len, **kw)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=NEW)
            for i, p in enumerate(prompts)]
    batcher.submit_many(reqs)
    batcher.run()
    _check(all(r.done and len(r.generated) == NEW for r in reqs),
           "a batcher stream ended short")
    return np.array([r.generated for r in reqs])


def _agree(ref_logits, logits, what: str, rel_tol: float):
    """Largest logit difference within ``rel_tol`` of the logit range,
    and the greedy token equal wherever the reference's top-2 gap is
    wider than twice that difference (closer pairs are near-ties).
    Returns (summary, the rows that were clear of a near-tie)."""
    d = float(np.max(np.abs(logits - ref_logits)))
    scale = float(np.max(np.abs(ref_logits)))
    _check(bool(np.isfinite(logits).all()) and d <= rel_tol * scale,
           f"{what}: max|d|={d} over {rel_tol} x max|ref|={scale}")
    top2 = np.sort(ref_logits, axis=-1)[:, -2:]
    clear = (top2[:, 1] - top2[:, 0]) > 2 * d
    same = ref_logits.argmax(-1) == logits.argmax(-1)
    _check(bool(same[clear].all()), f"{what}: greedy token differs on a "
           f"row without a near-tie")
    return f"max|d|={d:.4f} (of max|ref|={scale:.2f})", clear


def _same_streams(ref_tokens, tokens, clear, what: str) -> str:
    """Greedy streams against the reference's: the first two tokens
    equal on every row whose prefill and first decode step were both
    clear of a near-tie, and at least half of all tokens equal."""
    _check(bool((tokens[clear, :2] == ref_tokens[clear, :2]).all()),
           f"{what}: the first two greedy tokens differ on a row without "
           f"a near-tie")
    agree = float((tokens == ref_tokens).mean())
    _check(agree >= 0.5, f"{what}: greedy agreement {agree} under 0.5")
    return (f"greedy agreement {agree:.3f} over {NEW} tokens, first two "
            f"equal on {int(clear.sum())}/{len(clear)} clear rows")


def _row_err(out, ref) -> float:
    """Largest error of any row, as a share of that row's largest |ref|."""
    out = np.asarray(out, np.float32).reshape(ref.shape[0], -1)
    ref = np.asarray(ref, np.float32).reshape(ref.shape[0], -1)
    scale = np.maximum(np.max(np.abs(ref), axis=1), 1e-6)
    return float(np.max(np.max(np.abs(out - ref), axis=1) / scale))


def _kernel_parity(cfg, *, t=2048, s=1024, page=16, rel_tol=0.02) -> str:
    """Each attention kernel, through the wrapper the model calls, against
    its jnp reference on the chip, at ``cfg``'s attention widths. Decode
    rows are ragged: one visible position, tile and page edges, the
    last position. Errors are per row as a share of the row's largest
    |ref|: bf16 rounding stays well under ``rel_tol``, while a mask off
    by one moves the one-position row by all of it and a dropped tile
    moves a long row by a tile's share of its weight."""
    from repro.kernels.decode_attention import ops, ref
    from repro.kernels.decode_attention.quant import (dequantize_kv,
                                                      quantize_kv)
    from repro.kernels.flash_attention import (flash_attention,
                                               flash_attention_ref)

    b, h, kv, d = 8, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    q = jax.random.normal(ks[0], (b, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, t, kv, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, t, kv, d), jnp.bfloat16)
    lengths = jnp.array([0, 1, page - 1, page, 511, 512, t * 5 // 8,
                         t - 1], jnp.int32)
    (kq, kscale), (vq, vscale) = quantize_kv(k), quantize_kv(v)
    k8, v8 = dequantize_kv(kq, kscale), dequantize_kv(vq, vscale)
    # the same rows as a paged pool: logical page i of row r lives at a
    # shuffled physical page; page 0 stays the null page
    n_pages = b * (t // page)
    perm = 1 + np.random.default_rng(SEED).permutation(n_pages)
    table = jnp.asarray(perm.reshape(b, t // page), jnp.int32)

    def pool(x):
        pages = x.reshape(n_pages, page, *x.shape[2:])
        return jnp.zeros((n_pages + 1,) + pages.shape[1:],
                         x.dtype).at[perm].set(pages)

    def normalized(num, den, m):
        return num / jnp.maximum(den, 1e-30)[..., None]

    # the second of four sequence shards, as the seq-shard path sees it
    lo, hi = t // 4, t // 2
    qf = jax.random.normal(ks[3], (1, s, h, d), jnp.bfloat16)
    kf = jax.random.normal(ks[4], (1, s, kv, d), jnp.bfloat16)
    vf = jax.random.normal(ks[5], (1, s, kv, d), jnp.bfloat16)
    cases = {
        "decode": (lambda: ops.decode_attention(q, k, v, lengths),
                   lambda: ref.decode_attention_ref(q, k, v, lengths)),
        "decode int8": (
            lambda: ops.decode_attention(q, kq, vq, lengths, k_scale=kscale,
                                         v_scale=vscale),
            lambda: ref.decode_attention_ref(q, k8, v8, lengths)),
        "paged": (lambda: ops.paged_decode_attention(
                      q, pool(k), pool(v), lengths, table),
                  lambda: ref.decode_attention_ref(q, k, v, lengths)),
        "paged int8": (
            lambda: ops.paged_decode_attention(
                q, pool(kq), pool(vq), lengths, table,
                k_scale=pool(kscale), v_scale=pool(vscale)),
            lambda: ref.decode_attention_ref(q, k8, v8, lengths)),
        "partials": (
            lambda: normalized(*ops.decode_attention_partials(
                q, k[:, lo:hi], v[:, lo:hi], lengths, offset=lo)),
            lambda: normalized(*ref.decode_attention_partials_ref(
                q, k[:, lo:hi], v[:, lo:hi], lengths, offset=lo))),
        "flash": (lambda: flash_attention(qf, kf, vf, causal=True)[0],
                  lambda: flash_attention_ref(qf, kf, vf, causal=True)[0]),
    }
    parts = []
    for name, (kernel, reference) in cases.items():
        out = jax.jit(kernel)()
        with jax.default_matmul_precision("highest"):
            want = jax.jit(reference)()
        err = _row_err(out, want)
        _check(err <= rel_tol, f"{name} kernel: a row differs from the "
               f"reference by {err} of its largest |ref| (limit {rel_tol})")
        parts.append(f"{name} {err:.4f}")
    return (f"kernels vs jnp reference (b {b}, t {t}, flash s {s}), "
            f"largest per-row error: " + ", ".join(parts))


def phase_kernels(cfg, devices, *, n_rows=8, page_size=16):
    from repro.models import RunConfig, build
    from repro.serving import Engine

    t0, c0 = time.perf_counter(), _compile_s[0]
    parts = [_kernel_parity(cfg)]
    model = build(cfg)
    ref_engine = Engine(model, RunConfig(attn_impl="xla"))
    params = ref_engine.init_params(SEED)
    prompts = _prompts(n_rows, cfg.vocab_size, seed=SEED + 1)
    max_len = PROMPT + NEW + 8
    # name: (engine, batcher options, logit tolerance as a share of the
    # range); measured on a v5e: pallas 0.5%, paged int8 1.0% (int8 KV
    # adds per-token quantization error on top of bf16)
    variants = {
        "pallas": (Engine(model, RunConfig(attn_impl="pallas")), {},
                   0.015),
        "paged+int8": (Engine(model, RunConfig(attn_impl="pallas",
                                               kv_dtype="int8")),
                       {"paged": True, "page_size": page_size}, 0.03),
    }

    ref0, ref1, tok = _first_step_logits(ref_engine, params, prompts,
                                         max_len)
    ref_tokens = _streams(ref_engine, params, prompts, max_len)
    for name, (engine, kw, tol) in variants.items():
        if kw.get("paged"):
            l0, l1 = _paged_first_step_logits(engine, params, prompts, tok,
                                              page_size)
        else:
            l0, l1, _ = _first_step_logits(engine, params, prompts, max_len,
                                           tok)
        pre, clear0 = _agree(ref0, l0, f"{name} prefill", tol)
        dec, clear1 = _agree(ref1, l1, f"{name} decode", tol)
        toks = _streams(engine, params, prompts, max_len, **kw)
        parts.append(f"{name} vs xla: prefill {pre}, first decode {dec}, "
                     + _same_streams(ref_tokens, toks, clear0 & clear1,
                                     name))

    # the fused sampling kernel draws exactly the host sampler's tokens
    samp = dict(temperature=0.8, top_k=50, top_p=0.95, seed=SEED)
    engine = variants["pallas"][0]
    fused = _streams(engine, params, prompts, max_len, fused_sampling=True,
                     **samp)
    host = _streams(engine, params, prompts, max_len, **samp)
    _check(bool((fused == host).all()), "the fused sampling kernel drew "
           "other tokens than the host sampler at the same seed")
    parts.append(f"fused sampling kernel vs host sampler (T=0.8, top-k 50, "
                 f"top-p 0.95): {fused.size}/{host.size} tokens equal")
    _report("c kernels", cfg, t0, c0, devices,
            f"{n_rows} rows x prompt {PROMPT} + {NEW}: " + "; ".join(parts))


# ---------------------------------------------------------------------------
# (d) four chips
# ---------------------------------------------------------------------------


def phase_four_chips(full_cfg, cut_cfg, devices, *, n_rows=4):
    from repro.launch.mesh import make_host_mesh
    from repro.models import RunConfig, build
    from repro.models.common import param_bytes
    from repro.serving import Engine

    n = len(devices)
    mesh_flags = ["--mesh", f"1x{n}"]
    # --seq-shard splits the cache's sequence dim over "model"; a cache
    # of 128 positions a shard (run_http sizes it prompt + NEW + 8)
    # keeps each shard's block on the partials kernel (blocks under 64
    # positions take the jnp reference)
    max_len = 128 * n
    prompts = _prompts(n_rows, full_cfg.vocab_size, seed=SEED + 2,
                       length=max_len - NEW - 8)

    # the full model, sharded: no single chip could hold it with a cache
    t0, c0 = time.perf_counter(), _compile_s[0]
    out, toks = _serve_http(full_cfg, mesh_flags + [
        "--seq-shard", "--n-slots", str(n_rows), "--max-replicas", "1"],
        prompts)
    del out
    full_bytes = param_bytes(build(full_cfg).param_specs)
    peaks = [_peak(d) for d in devices]
    _check(max(peaks) < full_bytes / 2, f"the full model's params did "
           f"not spread over the mesh: peaks {peaks}")
    _report("d full model, --mesh 1x4 --seq-shard", full_cfg, t0, c0,
            devices, f"{n_rows} streams x {NEW} greedy tokens over HTTP "
            f"(prompt {prompts.shape[1]}), first row {toks[0][:8].tolist()}")
    gc.collect()

    # the cut meshless on one chip against the same cut on the mesh
    t0, c0 = time.perf_counter(), _compile_s[0]
    mesh = make_host_mesh((1, n), ("data", "model"))
    model = build(cut_cfg)
    one = Engine(model, RunConfig(cache_pad=16))
    params = one.init_params(SEED)
    sharded = Engine(model, RunConfig(cache_pad=16), mesh=mesh,
                     seq_shard=True)
    mparams = sharded.shard_params(params)
    ref0, ref1, tok = _first_step_logits(one, params, prompts, max_len)
    l0, l1, _ = _first_step_logits(sharded, mparams, prompts, max_len, tok)
    # measured on a v5e: 0.6% of the range
    pre, clear0 = _agree(ref0, l0, "mesh prefill", 0.015)
    dec, clear1 = _agree(ref1, l1, "mesh decode", 0.015)
    clear = clear0 & clear1
    t_one = _streams(one, params, prompts, max_len)
    t_mesh = _streams(sharded, mparams, prompts, max_len)
    _report("d cut, one chip vs 1x4 mesh", cut_cfg, t0, c0, devices,
            f"prefill {pre}, first decode {dec}, "
            + _same_streams(t_one, t_mesh, clear, "mesh"))
    del one, params, sharded, mparams
    gc.collect()

    # the cut as one replica per chip, each prompt sent once a replica
    t0, c0 = time.perf_counter(), _compile_s[0]
    out, toks = _serve_http(cut_cfg, mesh_flags + [
        "--mesh-slices", str(n), "--n-slots", str(n_rows),
        "--max-replicas", str(n)], np.concatenate([prompts] * n))
    rep = out["report"]
    used = sorted({i for ids in out["replica_devices"] for i in ids})
    _check(rep["peak_replicas"] == n
           and used == sorted(d.id for d in devices),
           f"mesh-slice replicas did not serve on every chip: "
           f"{out['replica_devices']}, {rep}")
    same = _same_streams(np.concatenate([t_one] * n), toks,
                         np.concatenate([clear] * n), "replicas")
    _report("d cut, --mesh 1x4 --mesh-slices 4", cut_cfg, t0, c0, devices,
            f"{rep['n_completed']}/{len(toks)} streams over HTTP on "
            f"{rep['peak_replicas']} replicas over devices {used}; vs the "
            f"one-chip streams: {same}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip phase (d)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform}); this check "
              f"runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    devices = devices[:args.chips]

    from repro.launch import compile_cache
    print(f"compile cache: {compile_cache.enable()}")
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    from repro import configs

    cut = cut_config()
    full = configs.get("qwen2-7b")
    print(f"cut: {cut.name} = qwen2-7b at {cut.n_layers} of "
          f"{full.n_layers} layers; widths as published (d_model "
          f"{cut.d_model}, {cut.n_heads} heads / {cut.n_kv_heads} kv, "
          f"head_dim {cut.head_dim}, d_ff {cut.d_ff}, vocab "
          f"{cut.vocab_size}); {cut.param_count_analytic() / 1e9:.2f} B "
          f"params of {full.param_count_analytic() / 1e9:.2f} B", flush=True)
    if args.chips == 4:
        phase_four_chips(full, cut, devices)
    else:
        phase_offline(configs.get("distilbert-imdb"), devices)
        gc.collect()
        phase_server(cut, devices)
        gc.collect()
        phase_kernels(cut, devices)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
