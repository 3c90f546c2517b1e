"""Serving driver: the paper's parallel batch inference, end to end.

OFFLINE mode (default) stands up the EFS-analogue store, publishes a
model, decomposes a batch job, and runs it monolithically AND in
parallel through the orchestrator with REAL inference on this host —
then prints the comparison the paper's Fig. 2 makes, plus
fault-tolerance statistics if faults are injected.

ONLINE mode (``--router``) puts LIVE traffic on the batched serving
stack instead: a synthetic arrival process (``--traffic
poisson|bursty|diurnal``) hits the ``repro.router`` arrival queue, and
each autoscaling policy in turn drives a replica pool of
``ContinuousBatcher`` instances — cold starts, optional crashes, and
per-policy TTFT/TPOT/goodput/cost on one line each.

HTTP mode (``--http``) is the real front door: an asyncio event loop
(``repro.router.frontdoor``) serves live streaming clients over
HTTP/1.1 — ``POST /v1/generate`` streams NDJSON token chunks as the
shared batched cache decodes them, TTFT/TPOT measured at real
first-token/per-token events, autoscaling and crash semantics identical
to the virtual-clock harness (same event core).

BATCH-DAG mode (``--batch-dag``) runs the offline job as an explicit
shard→prefill→decode→reduce DAG (``repro.batch``) on cloud-profiled
replica pools — heterogeneous spot/on-demand placement, deterministic
preemption survival with bit-identical outputs, optional ``--chaos``
ladder.

Usage (published widths; on a CPU add ``--arch distilbert-imdb-smoke``
or ``--router-arch qwen2-7b-smoke``; full qwen2-7b needs ``--mesh 1x4``):
  python -m repro.launch.serve --n-items 256 --batch-size 32 \
      --concurrency 8 --crash-prob 0.1
  python -m repro.launch.serve --batch-dag --dag-workers 6 \
      --spot-workers 4 --preempt-rate 0.25 --chaos
  python -m repro.launch.serve --router --traffic bursty --rate 24
  python -m repro.launch.serve --calibrate            # fit + save the
      # measured round-time model (router/calibrate.py artifact)
  python -m repro.launch.serve --router --calibration calibration.json \
      --mesh 2x4 --mesh-slices 2     # calibrated clock, replica-per-slice
  python -m repro.launch.serve --http --port 8765     # live front door
      # curl -N -d '{"prompt": [3,1,4,1,5], "max_new_tokens": 8}' \
      #     http://127.0.0.1:8765/v1/generate

Mesh mode: ``--mesh DxM`` (e.g. ``--mesh 2x4`` over 8 host devices, or
on TPU the real chips) lays a ("data", "model") mesh under every worker's
engine — params in the planner layout, inputs batch-sharded, and with
``--seq-shard`` the decode KV cache sequence-sharded over "model".

Models: ``--arch`` / ``--router-arch`` name a published config at its
published widths (``repro.configs.get``); ``<arch>-smoke`` names its
tiny-width preset for CPU runs, e.g. ``--router-arch qwen2-7b-smoke``.
Each mode is a function of (args, [mesh,] resolved ``ModelConfig``), so
a caller can run it with a config of its own (a depth cut, say).
Weights are random, made from ``--seed`` on the device(s).
"""
from __future__ import annotations

import argparse
import time

import jax

from repro import configs
from repro.core import (ArtifactStore, BatchJob, FaultInjector,
                        LatencyModel, MonolithicConfig, MonolithicRunner,
                        Orchestrator, OrchestratorConfig,
                        ServerlessFunction, decompose, merge)
from repro.data import imdb_reviews
from repro.data.pipeline import DatasetRef
from repro.launch import compile_cache
from repro.models import ModelConfig, RunConfig, build
from repro.serving import Engine


def run_router(args, mesh, cfg: ModelConfig):
    """Online mode: live traffic, per-policy TTFT/TPOT/cost rows.
    Also the home of ``--calibrate`` (measure + fit + save the round
    model on this host's engine, then use it if ``--router``)."""
    from repro.router import (CalibratedLatencyModel, QueueConfig,
                              ReplicaConfig, ReplicaPool, Router,
                              RouterConfig, TRAFFIC, default_policies,
                              fit_round_model, make_requests,
                              measure_round_samples)

    engine = Engine(build(cfg),
                    RunConfig(cache_pad=16, kv_dtype=args.kv_dtype),
                    mesh=mesh, seq_shard=args.seq_shard)
    params = engine.init_params(args.seed)
    store = ArtifactStore()
    store.put_tree("models/lm", params)

    cal = None
    cal_path = args.calibration or "calibration.json"
    if args.calibrate:
        samples = measure_round_samples(
            engine, params, prompt_lens=(args.prompt_len,
                                         2 * args.prompt_len),
            max_len=args.prompt_len * 2 + args.max_new_tokens + 8)
        cal = fit_round_model(samples, backend=jax.default_backend(),
                              device_count=jax.device_count(),
                              source="launch/serve.py --calibrate")
        cal.save(cal_path)
        print(f"== calibrated round model -> {cal_path}: "
              f"{cal.summary()} ==")
        if not args.router:
            return {"calibration": cal.to_json()}
    elif args.calibration:
        cal = CalibratedLatencyModel.load(cal_path)
        print(f"== loaded calibration {cal_path}: {cal.summary()} ==")
    if cal is not None and args.measured_time:
        raise SystemExit(
            "--measured-time conflicts with --calibrate/--calibration: "
            "the calibrated clock replaces measured wall time — drop one")

    arrivals = TRAFFIC[args.traffic](args.rate, args.horizon, args.seed)
    if cal is not None:
        # calibrated mode: the artifact carries the round constants —
        # LatencyModel.per_item_s must stay None (Router errors loudly
        # if both are supplied)
        lat = cal.to_latency_model(cold_start_s=args.cold_start)
        router_cfg = cal.to_router_config()
        per_token_s = cal.per_item_s
    else:
        lat = LatencyModel(cold_start_s=args.cold_start,
                           per_item_s=None if args.measured_time
                           else args.per_token_s)
        router_cfg = RouterConfig()
        per_token_s = args.per_token_s
    rcfg = ReplicaConfig(
        n_slots=args.n_slots,
        max_len=args.prompt_len + args.max_new_tokens + 8,
        fused_sampling=args.fused_sampling)
    # one replica retires ~1/per_token_s tokens of work per second (the
    # work-conserving time model — see router/README.md + COST_MODEL.md)
    policies = default_policies(slots_per_replica=args.n_slots,
                                max_replicas=args.max_replicas,
                                tokens_per_s_per_replica=1.0
                                / max(per_token_s, 1e-6),
                                budget_usd=args.budget_usd)
    print(f"== router: {len(arrivals)} requests over {args.horizon:.0f}s "
          f"({args.traffic} at {args.rate:.0f} rps), "
          f"prompt {args.prompt_len} + {args.max_new_tokens} new tokens, "
          f"{args.n_slots} slots/replica"
          + (f", {args.mesh_slices} mesh slices" if args.mesh_slices
             else "") + " ==")
    out = {}
    for policy in policies:
        traffic = make_requests(
            arrivals, prompt_len=args.prompt_len,
            max_new_tokens=args.max_new_tokens, vocab=cfg.vocab_size,
            seed=args.seed, deadline_s=args.deadline)
        pool = ReplicaPool(
            engine, params, rcfg, lat=lat,
            injector=FaultInjector(seed=args.seed,
                                   crash_prob=args.crash_prob,
                                   straggler_prob=args.straggler_prob),
            store=store, params_ref="models/lm",
            mesh_slices=args.mesh_slices)
        router = Router(pool, policy, traffic,
                        queue_cfg=QueueConfig(max_depth=args.queue_cap,
                                              default_deadline_s=
                                              args.deadline),
                        cfg=router_cfg, traffic_name=args.traffic)
        report = router.run()
        print(report.format_line())
        out[policy.name] = report.summary()
    return out


def run_batch_dag(args, cfg: ModelConfig):
    """Batch-DAG mode: the offline job as an explicit
    shard→prefill→decode→reduce DAG on cloud-profiled replica pools
    (repro.batch) — monolithic vs parallel, spot preemptions survived
    with bit-identical outputs, optional chaos ladder."""
    from repro.batch import (BatchDagRunner, PlacementPolicy, chaos_ladder,
                             inference_dag, make_dataset, make_group)
    from repro.router import ReplicaConfig
    from repro.router.cloud import ON_DEMAND, spot_profile
    from repro.router.events import VirtualClock

    engine = Engine(build(cfg), RunConfig(cache_pad=8))
    params = engine.init_params(args.seed)
    data = make_dataset(args.dag_items, prompt_len=args.prompt_len,
                        vocab=cfg.vocab_size,
                        max_new_tokens=args.max_new_tokens, seed=args.seed)
    rcfg = ReplicaConfig(n_slots=args.n_slots,
                         max_len=args.prompt_len + args.max_new_tokens)

    def groups(n_workers, kills=None, spot_workers=None):
        kills = kills or {}
        n_spot = (args.spot_workers if spot_workers is None
                  else spot_workers)
        n_od = max(n_workers - n_spot, 0)
        out = []
        if n_od:
            out.append(make_group(engine, params, ON_DEMAND, n_od,
                                  cfg=rcfg, extra_kills=kills.get(0, ())))
        if n_workers - n_od:
            sp = spot_profile(preempt_rate_per_s=args.preempt_rate,
                              seed=args.seed + 3)
            out.append(make_group(engine, params, sp, n_workers - n_od,
                                  cfg=rcfg,
                                  extra_kills=kills.get(len(out), ())))
        return out

    def run(shard_size, gs):
        dag = inference_dag(args.dag_items, shard_size)
        return BatchDagRunner(dag, data, gs, clock=VirtualClock(),
                              store=ArtifactStore(),
                              placement=PlacementPolicy(),
                              per_item_s=args.per_token_s,
                              task_overhead_s=0.02).run()

    print(f"== batch DAG: {args.dag_items} items, shard="
          f"{args.dag_shard_size}, {args.dag_workers} workers "
          f"({args.spot_workers} spot at {args.preempt_rate}/s) ==")
    # the baseline is always one ON-DEMAND worker: the paper's
    # "rent one big box" reference point is never preemptible
    mono = run(args.dag_items, groups(1, spot_workers=0))
    print(f"monolithic: wall={mono.wall_s:.2f}s busy={mono.busy_s:.2f}s "
          f"cost=${mono.cost_usd:.6f} tasks={mono.n_tasks}")
    par = run(args.dag_shard_size, groups(args.dag_workers))
    print(f"parallel:   wall={par.wall_s:.2f}s busy={par.busy_s:.2f}s "
          f"cost=${par.cost_usd:.6f} tasks={par.n_tasks} "
          f"preemptions={par.n_preemptions} spawns={par.n_spawns}")
    match = par.digest == mono.digest
    print(f"speedup: {mono.wall_s / par.wall_s:.2f}x | cost ratio "
          f"{par.cost_usd / max(mono.cost_usd, 1e-12):.3f} | outputs "
          f"{'identical' if match else 'DIVERGED'} | "
          f"compiles {mono.compile_count}->{par.compile_count}")
    out = {"mono": mono.summary(), "par": par.summary(),
           "outputs_identical": match}
    if args.chaos:
        reports, kills = chaos_ladder(
            lambda k: run(args.dag_shard_size,
                          groups(args.dag_workers, k)))
        # output parity only: with live spot pools the Poisson process
        # adds its own preemptions, so the exact fired-kill count
        # (n_preemptions == k, proven in tests/test_batch_dag.py on
        # on-demand pools) does not apply here
        parity = all(r.digest == reports[0].digest for r in reports)
        print(f"chaos ladder: {len(kills)} stage-boundary kills, "
              f"preemptions per rung "
              f"{[r.n_preemptions for r in reports]}, "
              f"parity={'OK' if parity else 'VIOLATED'} "
              f"(dup commits: "
              f"{max(r.n_duplicate_commits for r in reports)})")
        out["chaos"] = {"kills": len(kills), "parity": parity}
    return out


def run_http(args, mesh, cfg: ModelConfig, until=None):
    """Live HTTP mode: the asyncio front door over the event-driven
    router (wall clock, measured TTFT). Serves until interrupted, or —
    given ``until``, an ``async`` callable of the started front door —
    until that coroutine returns; its result is returned as
    ``"clients"``. With ``--mesh-slices`` each replica holds its own
    slice of ``mesh`` (``"replica_devices"`` lists their device ids)."""
    import asyncio

    from repro.core import LatencyModel
    from repro.obs import Observability, TraceRecorder
    from repro.router import (EventRouter, HttpFrontDoor, QueueConfig,
                              QueueDepthPolicy, ReplicaConfig, ReplicaPool,
                              WallClock)

    engine = Engine(build(cfg),
                    RunConfig(cache_pad=16, kv_dtype=args.kv_dtype),
                    mesh=mesh, seq_shard=args.seq_shard)
    params = engine.init_params(args.seed)
    pool = ReplicaPool(
        engine, params,
        ReplicaConfig(n_slots=args.n_slots,
                      max_len=args.prompt_len + args.max_new_tokens + 8,
                      fused_sampling=args.fused_sampling),
        # wall-clock serving measures time; modeled round constants are
        # the virtual harness's business (EventRouter raises on both)
        lat=LatencyModel(cold_start_s=args.cold_start, per_item_s=None),
        injector=FaultInjector(seed=args.seed, crash_prob=args.crash_prob,
                               straggler_prob=args.straggler_prob),
        mesh_slices=args.mesh_slices)
    obs = Observability(
        tracer=TraceRecorder() if args.trace else None)
    router = EventRouter(
        pool, QueueDepthPolicy(max_replicas=args.max_replicas),
        clock=WallClock(),
        queue_cfg=QueueConfig(max_depth=args.queue_cap,
                              default_deadline_s=args.deadline),
        traffic_name="http", obs=obs)
    door = HttpFrontDoor(router, host=args.host, port=args.port)

    async def _serve():
        await door.start()
        print(f"== serving on http://{args.host}:{door.port} — "
              f"POST /v1/generate, GET /healthz, GET /metrics "
              f"(Prometheus), GET /metrics.json ==")
        try:
            if until is None:
                await asyncio.Event().wait()      # until Ctrl-C
            return await until(door)
        finally:
            await door.close()
            print(router.report().format_line())
            if args.trace:
                t_dump = time.perf_counter()
                n = obs.tracer.dump(args.trace)
                print(f"== trace: {n} events -> {args.trace} in "
                      f"{time.perf_counter() - t_dump:.3f} s (analyze: "
                      f"python tools/trace_report.py {args.trace}) ==")

    out = {"port": door.port}
    try:
        out["clients"] = asyncio.run(_serve())
    except KeyboardInterrupt:
        pass
    out["report"] = router.report().summary()
    if pool.slices is not None:
        # which devices each replica ever spawned served on
        out["replica_devices"] = [
            [d.id for d in pool.slices.devices_of(r.slice_idx)]
            for r in pool.replicas]
    return out


def run_offline(args, mesh, cfg: ModelConfig):
    """Offline mode: the paper's job — classify a seeded IMDb-shaped
    dataset monolithically, then in parallel through the orchestrator
    (``ServerlessFunction`` → ``Engine.classify``). Returns both runs'
    summaries and their merged predictions."""
    engine = Engine(build(cfg), RunConfig(), mesh=mesh,
                    seq_shard=args.seq_shard)
    params = engine.init_params(args.seed)

    tokens, labels = imdb_reviews(n=args.n_items, seq_len=args.seq_len,
                                  vocab=cfg.vocab_size, seed=args.seed)
    store = ArtifactStore()
    store.put_tree("models/clf", params)
    job = BatchJob("serve", DatasetRef("imdb", args.n_items, args.seq_len,
                                       cfg.vocab_size),
                   "models/clf", args.batch_size)
    chunks = decompose(job)
    lat = LatencyModel(cold_start_s=0.2, per_item_s=None)  # real compute
    injector = FaultInjector(seed=args.seed, crash_prob=args.crash_prob,
                             straggler_prob=args.straggler_prob)

    def mk(i):
        return ServerlessFunction(i, store, lat, engine=engine,
                                  params_ref="models/clf")

    data = {"tokens": tokens}
    print(f"== job: {args.n_items} items, batch_size={args.batch_size}, "
          f"{len(chunks)} chunks ==")

    mono = MonolithicRunner(store, MonolithicConfig(),
                            injector=injector).run(job, chunks, mk,
                                                   data=data)
    mono_preds = merge(store, job, chunks)
    print(f"monolithic: wall={mono.wall_time_s:.1f}s "
          f"cost=${mono.cost_usd:.6f} chains={mono.n_invocations} "
          f"crashes={mono.n_crashes}")

    store2 = ArtifactStore()
    store2.put_tree("models/clf", params)
    orch = Orchestrator(
        store2,
        OrchestratorConfig(max_concurrency=args.concurrency,
                           retry_max_attempts=6, speculation_factor=3.0),
        injector=FaultInjector(seed=args.seed + 1,
                               crash_prob=args.crash_prob,
                               straggler_prob=args.straggler_prob))
    par = orch.run(job, chunks,
                   lambda i: ServerlessFunction(
                       i, store2, lat, engine=engine,
                       params_ref="models/clf"), data=data)
    preds = merge(store2, job, chunks)
    acc = float((preds == labels).mean())
    print(f"parallel:   wall={par.wall_time_s:.1f}s "
          f"cost=${par.cost_usd:.6f} fns={par.n_invocations} "
          f"retries={par.n_retries} spec={par.n_speculative} "
          f"crashes={par.n_crashes}")
    print(f"speedup: {mono.wall_time_s/par.wall_time_s:.1f}x | "
          f"cost ratio {par.cost_usd/max(mono.cost_usd,1e-12):.2f} | "
          f"predictions merged exactly-once, acc={acc:.3f} | "
          f"mono and parallel "
          f"{'identical' if (mono_preds == preds).all() else 'DIVERGED'}")
    return {"mono": mono.summary(), "par": par.summary(),
            "mono_preds": mono_preds, "par_preds": preds}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="distilbert-imdb")
    ap.add_argument("--n-items", type=int, default=256)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--crash-prob", type=float, default=0.0)
    ap.add_argument("--straggler-prob", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help='("data", "model") mesh shape, e.g. "2x4"; '
                         "requires that many local devices")
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-shard decode KV caches over 'model'")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=("bf16", "int8"),
                    help="decode KV cache dtype; int8 stores per-token "
                         "quantization scales alongside (single-host "
                         "only — conflicts with --mesh)")
    ap.add_argument("--fused-sampling", action="store_true",
                    help="draw each round's tokens inside the decode "
                         "dispatch (zero separate sampler dispatches); "
                         "same token streams as the host sampler at a "
                         "fixed seed")
    # -- online mode (repro.router) -------------------------------------
    ap.add_argument("--router", action="store_true",
                    help="online mode: live traffic through the "
                         "autoscaling router (ignores the offline "
                         "batch-job flags)")
    ap.add_argument("--traffic", default="poisson",
                    choices=("poisson", "bursty", "diurnal"))
    ap.add_argument("--rate", type=float, default=12.0,
                    help="arrival rate (requests/s; burst/peak rate for "
                         "bursty/diurnal)")
    ap.add_argument("--horizon", type=float, default=8.0,
                    help="traffic horizon in virtual seconds")
    ap.add_argument("--router-arch", default="qwen2-7b",
                    help="decoder LM for online generation and the batch "
                         "DAG (<arch>-smoke for the tiny preset)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--max-replicas", type=int, default=8)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request SLO seconds (goodput denominator)")
    ap.add_argument("--queue-cap", type=int, default=None,
                    help="admission control: reject past this depth")
    ap.add_argument("--cold-start", type=float, default=0.5,
                    help="replica cold-start seconds on the virtual clock")
    ap.add_argument("--per-token-s", type=float, default=0.02,
                    help="modeled seconds per decode token per slot")
    ap.add_argument("--measured-time", action="store_true",
                    help="advance the virtual clock by measured host "
                         "wall time instead of the token model")
    ap.add_argument("--calibrate", action="store_true",
                    help="measure round samples on this host's engine, "
                         "fit the round-time model (router/calibrate.py) "
                         "and save the artifact to --calibration; with "
                         "--router the run then uses it")
    ap.add_argument("--calibration", default=None, metavar="PATH",
                    help="CalibratedLatencyModel JSON to load for the "
                         "router run (written here by --calibrate; "
                         "default path calibration.json)")
    ap.add_argument("--mesh-slices", type=int, default=None,
                    help="replica-per-mesh-slice mode: partition the "
                         "--mesh into this many disjoint sub-meshes, "
                         "one per replica (dist.sharding.slice_meshes); "
                         "meshless engines degrade to independent "
                         "single-device engines")
    ap.add_argument("--budget-usd", type=float, default=1.0,
                    help="cost-cap policy budget")
    # -- batch-DAG mode (repro.batch) ------------------------------------
    ap.add_argument("--batch-dag", action="store_true",
                    help="offline batch job as an explicit shard/prefill/"
                         "decode/reduce DAG on cloud-profiled pools "
                         "(repro.batch): monolithic vs parallel, spot "
                         "preemptions survived with identical outputs")
    ap.add_argument("--dag-items", type=int, default=48,
                    help="batch-DAG dataset rows")
    ap.add_argument("--dag-shard-size", type=int, default=8,
                    help="rows per DAG shard (one prefill+decode chain "
                         "per shard)")
    ap.add_argument("--dag-workers", type=int, default=6,
                    help="total replicas for the parallel DAG run")
    ap.add_argument("--spot-workers", type=int, default=0,
                    help="of --dag-workers, how many come from a spot "
                         "pool (cheaper, preemptible)")
    ap.add_argument("--preempt-rate", type=float, default=0.25,
                    help="spot-pool preemption rate (kills per "
                         "worker-second of the Poisson process)")
    ap.add_argument("--chaos", action="store_true",
                    help="after the comparison, run the chaos ladder "
                         "(one deterministic kill per DAG stage "
                         "boundary; asserts output parity)")
    # -- HTTP front door (repro.router.frontdoor) ------------------------
    ap.add_argument("--http", action="store_true",
                    help="live serving mode: asyncio HTTP front door "
                         "over the event-driven router (wall clock, "
                         "measured TTFT); POST /v1/generate streams "
                         "NDJSON token chunks")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8765,
                    help="HTTP front-door port (0 = ephemeral)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record per-request trace spans (repro.obs "
                         "JSONL) and write them here on shutdown; "
                         "analyze with tools/trace_report.py")
    return ap


def mesh_from_args(args):
    """The ("data", "model") mesh ``--mesh DxM`` names, or None."""
    if not args.mesh:
        return None
    from repro.launch.mesh import make_host_mesh
    shape = tuple(int(x) for x in args.mesh.lower().split("x"))
    return make_host_mesh(shape, ("data", "model"))


def main(argv=None):
    args = build_parser().parse_args(argv)
    compile_cache.enable()

    mesh = mesh_from_args(args)
    if args.http:
        return run_http(args, mesh, configs.get(args.router_arch))
    if args.batch_dag:
        return run_batch_dag(args, configs.get(args.router_arch))
    if args.router or args.calibrate:
        return run_router(args, mesh, configs.get(args.router_arch))
    return run_offline(args, mesh, configs.get(args.arch))


if __name__ == "__main__":
    main()
