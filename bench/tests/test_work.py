"""Operations, bytes and peaks from shapes."""
import dataclasses

import pytest

import harness
import work


def _cell(name):
    import json
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    return harness.resolve(bench, name)


@pytest.mark.parametrize("workload,params", [
    ("distilbert-imdb.offline-512", 66.9e6),
    ("qwen2-7b-8L.chat-steady", 2.95e9),
])
def test_parameter_counts_match_the_program(workload, params):
    from repro.models import build
    from repro.models.common import param_count
    cell = _cell(workload)
    model = harness.load_module(harness.HERE / "models" /
                                f"{cell.config['kind']}.py")
    layout = model.layout(cell.config)
    program = param_count(build(harness.program_config(cell.config))
                          .param_specs)
    assert work.n_params(layout) == program
    assert abs(program - params) / params < 0.01


def test_decode_bytes_count_live_positions_not_capacity():
    cell = _cell("qwen2-7b-8L.chat-steady")
    c = cell.config
    model = harness.load_module(harness.HERE / "models" / "decoder_lm.py")
    layout = model.layout(c)
    per_pos = work.kv_bytes_per_position(c)
    assert per_pos == 2 * 8 * 4 * 128 * 2           # 16 KiB a position
    # one step, 64 rows of 100 live positions each, in a 1,544 cache:
    # the bytes grow with the live positions only
    small = work.decode_bytes(c, layout, 1, 64, 64 * 100)
    large = work.decode_bytes(c, layout, 1, 64, 64 * 200)
    assert large - small == pytest.approx(64 * 100 * per_pos)
    weights = work.weight_bytes(layout, skip=("embed",))
    assert small == pytest.approx(weights + 64 * (2 * 3584 + per_pos)
                                  + 6400 * per_pos)


def test_classify_flops_per_review():
    c = _cell("distilbert-imdb.offline-512").config
    assert work.classify_flops(c, 512) == pytest.approx(48.3e9, rel=0.01)


def test_peaks_known_and_unknown():
    assert work.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
