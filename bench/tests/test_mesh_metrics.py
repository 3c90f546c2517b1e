"""The four-chip cell: its files resolve by name, its configuration and
mix agree on the mesh, and its three device-trace metrics read a small
synthetic trace of four devices as worked out by hand.

Device traces are in nanoseconds; the window (``bench:window``) is
[0, 1e9), one second.
"""
import json

import pytest

import harness
import mesh_work
import trace_reduce
import work

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELL = "qwen2-7b-1x4.chat-steady"
NEW = ["step_mfu.mesh", "collective_exposed_share.online",
       "decode_attention_partials_roofline.mesh"]
JOINED = ["ttft_p95_ms.online", "queue_wait_p95_ms.online",
          "batch_occupancy.online", "device_idle_share.online",
          "stream_lag_p95_ms.online", "first_token_sent_p95_ms.online",
          "frontdoor_idle_share.online", "prefill_share.online"]
KIND = "TPU v5 lite"
PEAK, BW = 197e12, 819e9
DEVS = [f"/device:TPU:{k}" for k in range(4)]
TINY = {"hidden_size": 64, "num_attention_heads": 8,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "num_hidden_layers": 2, "vocab_size": 512, "dtype": "bfloat16"}


def _cell():
    return harness.resolve(BENCH, CELL)


def _read(metric, rec, cell):
    return cell.module("metrics", metric).read(rec, cell)


def _record(devices, tokens, cell, traced=(100.0, 101.0)):
    trace = {"devices": devices, "host": [("bench:window", 0.0, 1e9)]}
    return harness.Record(
        end_to_end={}, checks=[], attempted=0, failed=0,
        memory_peak_bytes=0, trace=trace,
        facts={"traced": traced, "tokens": tokens, "device_kind": KIND,
               "layout": cell.module("models", "decoder_lm").layout(
                   cell.config)})


# 20 decode tokens in the traced part [100, 101) of the clients' clock,
# at context lengths 200 + 1..20; one prefill token (j = 0) and one
# token outside the traced part, both left out
TOKENS = [(100.0 + 0.04 * k, 200, k + 1) for k in range(20)] + \
    [(100.5, 300, 0), (99.0, 200, 5)]
N_TOK, SUM_CTX = 20, float(sum(200 + k + 1 for k in range(20)))


def test_the_cell_resolves_its_config_mix_and_metrics():
    c = _cell()
    assert c.chips == 4
    assert c.config["name"] == "qwen2-7b-1x4"
    assert c.config["num_hidden_layers"] == 28 and c.config["reduced"] == []
    assert c.traffic["driver"] == "online" and c.traffic["trace_s"] == 3
    assert {m["name"] for m in c.end_to_end} == {"itl_p95_ms",
                                                 "tokens_per_s", "setup_s"}
    names = [m["name"] for m in c.per_layer]
    assert sorted(names) == sorted(NEW + JOINED)
    assert "step_mfu.online" not in names     # it reckons one chip
    for m in c.per_layer:
        assert callable(c.module("metrics", m["name"]).read)
    harness.program_config(c.config)


def test_the_config_is_the_8_layer_one_at_its_published_depth():
    whole = _cell().config
    cut = harness.resolve(BENCH, "qwen2-7b-8L.chat-steady").config
    differ = {k for k in set(whole) | set(cut) if whole.get(k) != cut.get(k)}
    assert differ == {"name", "num_hidden_layers", "reduced", "published",
                      "chips", "mesh", "deployment"}
    assert cut["published"]["num_hidden_layers"] == 28


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]
                                  if w["traffic"].startswith("chat")])
def test_config_chips_and_mesh_agree_with_the_mix(cell):
    c = harness.resolve(BENCH, cell)
    server = c.traffic["server"]
    assert c.config["chips"] == c.chips
    if c.config["mesh"] is None:
        assert "--mesh" not in server and c.chips == 1
        return
    assert server[server.index("--mesh") + 1] == c.config["mesh"]
    data, model = map(int, c.config["mesh"].split("x"))
    assert data * model == c.chips
    assert "--seq-shard" in server


def _decode_devices(step_ns, runs, n_dev):
    # each device runs the decode executable ``runs`` times in the
    # window, and a prefill that is not the decode step
    return {d: {"ops": [], "modules":
                [(f"jit__ds({k})", 1e7 + k * 5e7, 1e7 + k * 5e7 + step_ns)
                 for k in range(runs)]
                + [("jit__prefill_into(2)", 9.5e8, 9.9e8)]}
            for d in DEVS[:n_dev]}


def test_step_mfu_mesh_counts_each_step_once_over_four_chips_peak():
    c = _cell()
    # 10 decode steps of 20 ms on each of four devices
    rec = _record(_decode_devices(2e7, 10, 4), TOKENS, c)
    flops = work.decode_flops(c.config, N_TOK, SUM_CTX)
    nbytes = work.decode_bytes(c.config, rec.facts["layout"], 10, N_TOK,
                               SUM_CTX)
    least = max(flops / (4 * PEAK), nbytes / (4 * BW))
    got = _read("step_mfu.mesh", rec, c)
    assert got == pytest.approx(100.0 * least / 0.2)
    # the same steps on one device: one chip's peak, a quarter the share
    one = _record(_decode_devices(2e7, 10, 1), TOKENS, c)
    assert _read("step_mfu.mesh", one, c) == pytest.approx(4 * got)


def test_collective_exposed_share_is_exposed_collective_time_over_window():
    c = _cell()
    # device k: an all-gather [100, 400) ms beside compute [300, 350)
    # and [380 + 5 k, 600) ms, and an all-reduce from 950 ms on past
    # the window's end
    devs = {}
    for k, d in enumerate(DEVS):
        devs[d] = {"modules": [], "ops": [
            ("all-gather.3", 1e8, 4e8),
            ("fusion.1", 3e8, 3.5e8),
            ("fusion.2", 3.8e8 + 5e6 * k, 6e8),
            ("all-reduce", 9.5e8, 1.2e9)]}        # half past the window
    # exposed: [100, 300) + [350, 380 + 5 k) + [950, 1000) ms
    want = sum(200 + 30 + 5 * k + 50 for k in range(4)) / 4 / 1000
    rec = _record(devs, TOKENS, c)
    got = _read("collective_exposed_share.online", rec, c)
    assert got == pytest.approx(100.0 * want)
    lo, hi = trace_reduce.window(rec.trace)
    assert got == pytest.approx(100.0 * trace_reduce.exposed_collective_s(
        rec.trace, lo, hi) / 1.0)
    # the layer loop's ``while`` spans its collectives and its compute:
    # it hides nothing
    for v in devs.values():
        v["ops"].append(("while.2", 0.0, 9e8))
    assert _read("collective_exposed_share.online", rec, c) == \
        pytest.approx(100.0 * want)


def test_partials_work_from_shapes():
    # 2 layers, 8 heads of 8, 4 KV heads, bf16: a live position is
    # 2 (k, v) x 2 layers x 4 x 8 x 2 bytes = 256; per token and chip a
    # layer reads its query (8 x 8 x 2 = 128 bytes) and writes its
    # float32 partials (8 x (8 + 2) x 4 = 320 bytes)
    assert mesh_work.partials_flops(TINY, 1000) == 4 * 2 * 8 * 8 * 1000
    assert mesh_work.partials_bytes(TINY, 4, 10, 1000) == \
        1000 * 256 + 10 * 4 * 2 * (128 + 320)


def test_partials_roofline_follows_its_operations_and_bytes():
    c = _cell()
    # each device runs the kernel 40 times for 1 ms (one run half outside
    # the window), beside other ops that do not count
    devs = {d: {"modules": [], "ops":
                [(f"decode_attention_partials.{k}", 2e7 * k, 2e7 * k + 1e6)
                 for k in range(40)]
                + [("%decode_attention_partials.7 = f32[1] custom-call()",
                    9.995e8, 1.0005e9),
                   ("fusion.4", 1e6, 5e6), ("all-gather", 5e6, 6e6)]}
            for d in DEVS}
    rec = _record(devs, TOKENS, c)
    kern_s = 4 * (40 * 1e-3 + 0.5e-3)
    flops = mesh_work.partials_flops(c.config, SUM_CTX)
    nbytes = mesh_work.partials_bytes(c.config, 4, N_TOK, SUM_CTX)
    least = max(flops / (4 * PEAK), nbytes / (4 * BW))
    got = _read("decode_attention_partials_roofline.mesh", rec, c)
    assert got == pytest.approx(100.0 * least / (kern_s / 4))
    assert 0.0 < got < 100.0


@pytest.mark.parametrize("metric", NEW)
def test_mesh_metrics_read_nothing_where_there_is_nothing(metric):
    c = _cell()
    assert _read(metric, _record({}, TOKENS, c), c) is None  # no device
    rec = _record({}, TOKENS, c)
    rec.trace = None                                          # trace off
    assert _read(metric, rec, c) is None
    if metric != "collective_exposed_share.online":
        # a device trace without the decode step or the kernel
        bare = {d: {"modules": [], "ops": [("fusion", 0.0, 1e8)]}
                for d in DEVS}
        assert _read(metric, _record(bare, TOKENS, c), c) is None
