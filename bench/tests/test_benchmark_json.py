"""``BENCHMARK.json`` resolves by name, keeps to its character rules, and
takes a new cell, configuration, mix or metric as files and entries
alone."""
import json
import re
import shutil

import pytest

import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_names_and_units():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [x["name"] for x in BENCH["configs"] + BENCH["workloads"]
             + metrics]
    assert all(NAME.match(n) for n in names), names
    assert len(set(x["name"] for x in metrics)) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in BENCH["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_by_name(cell):
    c = harness.resolve(BENCH, cell)
    assert (c.bench_dir / "models" / f"{c.config['kind']}.py").exists()
    assert (c.bench_dir / "drivers" / f"{c.traffic['driver']}.py").exists()
    for m in c.per_layer:
        assert callable(c.module("metrics", m["name"]).read)
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer
    harness.program_config(c.config)   # the program takes the config


def test_each_metric_moves_what_its_cells_report():
    for m in BENCH["per_layer"]:
        for cell in m.get("workloads", CELLS):
            reported = {e["name"] for e in harness.resolve(
                BENCH, cell).end_to_end}
            assert m["moves"] in reported, (m["name"], cell)


def test_config_files_state_their_cut():
    for conf in BENCH["configs"]:
        data = harness.load_json(harness.ROOT / conf["file"])
        assert data["name"] == conf["name"]
        assert data["source"] == conf["source"]
        assert sorted(data["reduced"]) == sorted(conf["reduced"])
        for key in conf["reduced"]:
            assert key in data["published"], key


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A copy of the checkout gains a mix, a metric and a cell without a
    change to any file it had."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    mix = json.loads((root / "bench" / "traffic" / "chat-steady.json")
                     .read_text())
    mix["rate_rps"] = 2.0
    (root / "bench" / "traffic" / "chat-slow.json").write_text(
        json.dumps(mix))
    (root / "bench" / "metrics" / "prompt_tokens.online.py").write_text(
        "def read(rec, cell):\n    return 1.0\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "qwen2-7b-8L.chat-slow",
                               "config": "qwen2-7b-8L",
                               "traffic": "chat-slow", "chips": 1,
                               "why": "a slower mix"})
    bench["per_layer"].append({"name": "prompt_tokens.online", "unit": "1",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "batcher (serving/batching.py)",
                               "moves": "tokens_per_s",
                               "workloads": ["qwen2-7b-8L.chat-slow"]})
    cell = harness.resolve(bench, "qwen2-7b-8L.chat-slow", root)
    assert cell.traffic["rate_rps"] == 2.0
    assert [m["name"] for m in cell.per_layer] == ["prompt_tokens.online"]
    assert cell.module("metrics", "prompt_tokens.online").read(None,
                                                              cell) == 1.0
    assert all(p.read_bytes() == b for p, b in before.items())
