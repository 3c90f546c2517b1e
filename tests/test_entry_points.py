"""Entry points: config names, the compile cache's placement, the
offline job through ``repro.launch.serve``, and ``chip_smoke.py``'s
refusal to run off a TPU."""
import asyncio
import importlib.util
import json
import pathlib

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import (ArtifactStore, BatchJob, LatencyModel,
                        ServerlessFunction, decompose)
from repro.data import imdb_reviews
from repro.data.pipeline import DatasetRef
from repro.launch import compile_cache, serve
from repro.models import RunConfig, build
from repro.serving import Engine

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("arch", ["qwen2-7b", "distilbert-imdb"])
def test_smoke_preset_resolves_by_name(arch):
    full = configs.get(arch)
    smoke = configs.get(arch + "-smoke")
    assert smoke == configs.reduce_for_smoke(full)
    assert smoke.name == arch + "-smoke" and smoke.d_model < full.d_model
    assert full.name == arch
    assert configs.smoke(arch) == smoke
    with pytest.raises(KeyError):
        configs.get(arch + "-tiny")
    with pytest.raises(KeyError):
        configs.smoke(arch + "-smoke")      # no preset of a preset


def test_compile_cache_goes_where_the_env_says(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == was   # left to JAX
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert compile_cache.enable() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_worker_places_params_on_device_once():
    cfg = configs.get("distilbert-imdb-smoke")
    engine = Engine(build(cfg), RunConfig())
    store = ArtifactStore()
    store.put_tree("m", engine.init_params(0))
    job = BatchJob("j", DatasetRef("d", 16, 8, cfg.vocab_size), "m", 8)
    tokens = imdb_reviews(n=16, seq_len=8, vocab=cfg.vocab_size, seed=0)[0]
    fn = ServerlessFunction(0, store, LatencyModel(per_item_s=None),
                            engine=engine, params_ref="m")
    c0, c1 = decompose(job)
    assert fn.invoke(job, c0, {"tokens": tokens}).cold_start
    placed = fn._params
    assert all(isinstance(x, jax.Array)
               for x in jax.tree.leaves(placed))
    assert not fn.invoke(job, c1, {"tokens": tokens}).cold_start
    assert fn._params is placed           # warm call: no second upload


def test_offline_job_mono_and_parallel_predictions_identical():
    cfg = configs.get("distilbert-imdb-smoke")
    args = serve.build_parser().parse_args([
        "--arch", cfg.name, "--n-items", "48", "--seq-len", "16",
        "--batch-size", "8", "--concurrency", "4", "--crash-prob", "0.2"])
    out = serve.run_offline(args, None, cfg)
    assert out["mono_preds"].shape == (48,)
    assert (out["mono_preds"] >= 0).all()
    np.testing.assert_array_equal(out["mono_preds"], out["par_preds"])


async def _generate(port, prompt, n_new):
    """One streaming HTTP client; returns its NDJSON chunks."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps({"prompt": prompt, "max_new_tokens": n_new})
    writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n{body}").encode())
    await writer.drain()
    assert b"200" in await reader.readline()
    while (await reader.readline()) not in (b"\r\n", b"\n"):
        pass
    chunks = []
    while size := int((await reader.readline()).strip() or b"0", 16):
        chunks.append(json.loads(await reader.readexactly(size)))
        await reader.readexactly(2)
    writer.close()
    return chunks


def test_http_mode_holds_replicas_to_mesh_slices():
    """``--http --mesh-slices 2`` caps the pool at two replicas (one per
    slice) even when the queue asks for more."""
    cfg = configs.get("qwen2-7b-smoke")
    args = serve.build_parser().parse_args([
        "--http", "--port", "0", "--mesh-slices", "2", "--n-slots", "1",
        "--max-replicas", "4", "--cold-start", "0", "--prompt-len", "8",
        "--max-new-tokens", "3"])

    async def clients(door):
        return await asyncio.gather(*(_generate(door.port, [1 + i] * 8, 3)
                                      for i in range(4)))

    out = serve.run_http(args, None, cfg, until=clients)
    assert [c[-1]["n_tokens"] for c in out["clients"]] == [3] * 4
    assert out["report"]["n_completed"] == 4
    assert out["report"]["peak_replicas"] == 2
    assert len(out["replica_devices"]) >= 2


def test_chip_smoke_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
