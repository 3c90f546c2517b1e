import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above run before ANY other import (jax locks the device
count at first init): the dry-run — and only the dry-run — sees 512
placeholder host devices so the production meshes (16,16) and (2,16,16)
can be built.

Per cell this driver:
  1. builds the model from its full production config (ShapeDtypeStruct
     stand-ins only — zero allocation),
  2. plans shardings (dist.sharding strategy auto-pick),
  3. jit-lowers and compiles train_step / prefill / serve_step under the
     production mesh,
  4. records memory_analysis (fits-per-chip proof), cost_analysis, and
     the while-aware HLO roofline terms (launch/hlo_analysis),
  5. writes a JSON artifact consumed by benchmarks/roofline.py and
     EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod both|on|off]
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.dist import context as dist_ctx
from repro.dist import sharding as shd
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh, n_chips
from repro.models import RunConfig, SkipCell, build
from repro.models.common import param_bytes
from repro.models.model_zoo import SHAPES
from repro.training.optimizer import Adafactor, AdamW, constant
from repro.training.train_step import make_train_step

# TPU v5e hardware constants (DESIGN.md §7)
PEAK_FLOPS = 197e12       # bf16 per chip
HBM_BW = 819e9            # bytes/s per chip
ICI_BW = 50e9             # bytes/s per link

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def default_run(kind: str, cfg, strategy: str,
                overrides: Optional[dict] = None) -> RunConfig:
    if kind == "train":
        if strategy == "fsdp":
            # pure-FSDP small models: no TP all-reduces; batch over all
            # chips, full remat, no grad accumulation (§Perf iteration 3)
            run = RunConfig(attn_impl="xla", moe_impl="auto", remat="full",
                            microbatch=None)
        else:
            # seq_parallel measured a wash for train (M 2.5x better but
            # GSPMD pays the AG without dropping the AR -> X 1.5x worse,
            # §Perf iteration 10) — keep it off; on for prefill below.
            run = RunConfig(attn_impl="xla", moe_impl="auto", remat="dots",
                            microbatch=32)
    elif kind == "prefill":
        run = RunConfig(attn_impl="xla", moe_impl="auto",
                        seq_parallel=(cfg.moe is None
                                      and not cfg.attention_free))
    else:  # decode
        seq_shard = not cfg.attention_free
        run = RunConfig(attn_impl="seq_shard" if seq_shard else "xla",
                        moe_impl="auto")
    if overrides:
        run = dataclasses.replace(run, **overrides)
    return run


def model_flops_analytic(model, shape: str) -> float:
    """6·N·D (train) / 2·N·D (inference), N = active params, D = tokens."""
    seq, gb, kind = SHAPES[shape]
    n = model.active_param_count
    tokens = gb * seq if kind != "decode" else gb * 1
    return (6.0 if kind == "train" else 2.0) * n * tokens


def build_step(model, kind: str, run: RunConfig, mesh, strategy: str,
               inputs, cache):
    """Returns (jitted_fn, example_args) ready to .lower(*args)."""
    cfg = model.cfg
    p_abs = model.abstract()
    p_spec = shd.param_specs_tree(model.param_specs, strategy, mesh)
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), p_spec)
    in_sh = shd.input_shardings(inputs, mesh)

    if kind == "train":
        # ≥100B params: fp32 Adam states are 12 bytes/param = 4 TB for a
        # 340B model — more than a 256-chip pod's HBM even fully sharded.
        # Factored second moments (Adafactor) make the cell feasible
        # (§Perf iteration 11 / §Dry-run fit notes).
        if param_bytes(model.param_specs) > 200e9:  # >100B bf16 params
            opt = Adafactor(schedule=constant(1e-4))
            opt_abs = jax.eval_shape(opt.init, p_abs)
            # factored row/col stats are ~1/dim the size of params:
            # replicated shardings are fine (tens of MB per chip)
            opt_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, P()), opt_abs)
        else:
            opt = AdamW(schedule=constant(1e-4))
            opt_abs = jax.eval_shape(opt.init, p_abs)
            opt_sh = {
                "m": p_sh, "v": p_sh, "master": p_sh,
                "step": NamedSharding(mesh, P()),
            }
        step = make_train_step(model, run, opt, mesh=mesh)
        fn = jax.jit(step,
                     in_shardings=(p_sh, opt_sh, in_sh),
                     donate_argnums=(0, 1))
        return fn, (p_abs, opt_abs, inputs)

    if kind == "prefill":
        seq_shard = not cfg.attention_free

        def prefill_fn(params, batch):
            logits, c = model.prefill(run, params, batch)
            return logits, c

        cache_abs = jax.eval_shape(
            lambda p, b: model.prefill(run, p, b)[1], p_abs, inputs)
        cache_sh = shd.cache_shardings(cache_abs, cfg, mesh,
                                       seq_shard=seq_shard)
        logits_sh = NamedSharding(
            mesh, shd.sanitize_spec(
                P(dist_ctx.dp_axes(mesh), "model"),
                (jax.tree.leaves(inputs)[0].shape[0], cfg.vocab_size),
                mesh))
        fn = jax.jit(prefill_fn, in_shardings=(p_sh, in_sh),
                     out_shardings=(logits_sh, cache_sh))
        return fn, (p_abs, inputs)

    # decode / serve_step
    seq_shard = run.attn_impl == "seq_shard"
    cache_sh = shd.cache_shardings(cache, cfg, mesh, seq_shard=seq_shard)

    def serve_step(params, c, batch):
        logits, c2 = model.decode_step(run, params, c, batch)
        return logits, c2

    gb = jax.tree.leaves(inputs)[0].shape[0]
    logits_sh = NamedSharding(
        mesh, shd.sanitize_spec(P(dist_ctx.dp_axes(mesh), "model"),
                                (gb, cfg.vocab_size), mesh))
    fn = jax.jit(serve_step,
                 in_shardings=(p_sh, cache_sh, in_sh),
                 out_shardings=(logits_sh, cache_sh),
                 donate_argnums=(1,))
    return fn, (p_abs, cache, inputs)


def run_cell(arch: str, shape: str, *, multi_pod: bool = False,
             run_overrides: Optional[dict] = None,
             strategy: Optional[str] = None,
             tag: str = "", verbose: bool = True) -> dict:
    cfg = configs.get(arch)
    model = build(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = n_chips(mesh)
    rec = {
        "arch": arch, "shape": shape, "multi_pod": multi_pod,
        "chips": chips, "tag": tag,
        "params": model.n_params, "active_params": model.active_param_count,
    }
    seq, gb, kind = SHAPES[shape]
    rec.update(seq_len=seq, global_batch=gb, kind=kind)
    try:
        strat = strategy or shd.pick_strategy(model.param_specs, mesh, kind)
        if strat == "fsdp" and gb % chips != 0:
            # pure FSDP shards the batch over every chip; with
            # global_batch < chips the constraints would drop batch
            # sharding and replicate all compute (measured 1.5 TB/chip on
            # the 2-pod mesh) — fall back to ZeRO-3 + TP.
            strat = "fsdp_tp"
        run = default_run(kind, cfg, strat, run_overrides)
        try:
            if strat == "fsdp":  # batch shards over every mesh axis
                dist_ctx.set_batch_axes(("pod", "data", "model"))
            with dist_ctx.mesh_context(mesh):
                kind, inputs, cache = model.input_specs(shape, run)
                rec["strategy"] = strat
                rec["run"] = dataclasses.asdict(run)
                fn, args = build_step(model, kind, run, mesh, strat, inputs,
                                      cache)
                t0 = time.time()
                lowered = fn.lower(*args)
                rec["lower_s"] = round(time.time() - t0, 2)
                t0 = time.time()
                compiled = lowered.compile()
                rec["compile_s"] = round(time.time() - t0, 2)
        finally:
            dist_ctx.set_batch_axes(None)

        ma = compiled.memory_analysis()
        if ma is not None:
            rec["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
            }
            live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                    + ma.output_size_in_bytes - ma.alias_size_in_bytes)
            rec["memory"]["live_bytes_per_chip"] = int(live)
            rec["memory"]["fits_16g_hbm"] = bool(live <= 16 * 2**30)
        ca = compiled.cost_analysis() or {}
        rec["cost_analysis"] = {
            "flops_scan_once": float(ca.get("flops", 0.0)),
            "bytes_scan_once": float(ca.get("bytes accessed", 0.0)),
        }
        an = hlo_analysis.analyze_hlo(compiled.as_text())
        rec["hlo"] = {
            "flops_per_chip": an.flops,
            "hbm_bytes_per_chip": an.hbm_bytes,
            "collective_bytes_per_chip": an.total_collective_bytes,
            "collective_by_type": dict(an.collective_bytes),
            "collective_instances": dict(an.collective_instances),
            "while_trips": an.while_trips,
            "n_dots": an.n_dots,
        }
        compute_s = an.flops / PEAK_FLOPS
        memory_s = an.hbm_bytes / HBM_BW
        coll_s = an.total_collective_bytes / ICI_BW
        dominant = max((compute_s, "compute"), (memory_s, "memory"),
                       (coll_s, "collective"))[1]
        mf = model_flops_analytic(model, shape)
        rec["roofline"] = {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": coll_s,
            "dominant": dominant,
            "bound_s": max(compute_s, memory_s, coll_s),
            "roofline_fraction": compute_s / max(compute_s, memory_s,
                                                 coll_s, 1e-30),
            "model_flops_total": mf,
            "model_flops_per_chip": mf / chips,
            "useful_flops_ratio": (mf / chips) / max(an.flops, 1e-30),
        }
        rec["status"] = "ok"
        if verbose:
            r = rec["roofline"]
            print(f"[ok] {arch:24s} {shape:12s} pod={int(multi_pod)+1} "
                  f"{strat:8s} compile={rec['compile_s']:6.1f}s "
                  f"C={r['compute_s']*1e3:9.2f}ms M={r['memory_s']*1e3:9.2f}ms "
                  f"X={r['collective_s']*1e3:9.2f}ms -> {r['dominant']}"
                  f" frac={r['roofline_fraction']:.3f}")
    except SkipCell as e:
        rec["status"] = "skip"
        rec["skip_reason"] = str(e)
        if verbose:
            print(f"[skip] {arch:24s} {shape:12s}: {e}")
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[ERR] {arch:24s} {shape:12s}: {type(e).__name__}: "
                  f"{str(e)[:200]}")
    return rec


def save_artifact(rec: dict, out_dir: str = ARTIFACT_DIR):
    os.makedirs(out_dir, exist_ok=True)
    pod = "pod2" if rec["multi_pod"] else "pod1"
    tag = f"__{rec['tag']}" if rec.get("tag") else ""
    path = os.path.join(
        out_dir, f"{rec['arch']}__{rec['shape']}__{pod}{tag}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=2, default=str)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", default="off", choices=["off", "on",
                                                           "both"])
    ap.add_argument("--strategy", default=None)
    ap.add_argument("--attn-impl", default=None)
    ap.add_argument("--moe-impl", default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    args = ap.parse_args()

    overrides = {}
    for k, v in [("attn_impl", args.attn_impl), ("moe_impl", args.moe_impl),
                 ("remat", args.remat), ("microbatch", args.microbatch)]:
        if v is not None:
            overrides[k] = v

    pods = {"off": [False], "on": [True], "both": [False, True]}[
        args.multi_pod]
    archs = configs.ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    n_ok = n_skip = n_err = 0
    for multi_pod in pods:
        for arch in archs:
            for shape in shapes:
                rec = run_cell(arch, shape, multi_pod=multi_pod,
                               run_overrides=overrides or None,
                               strategy=args.strategy, tag=args.tag)
                save_artifact(rec, args.out)
                n_ok += rec["status"] == "ok"
                n_skip += rec["status"] == "skip"
                n_err += rec["status"] == "error"
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skip, {n_err} error")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
