"""The traffic generator: one seed gives one schedule, another seed the
same work in another order."""
import json

import numpy as np
import pytest

import gen
import harness

BIG = 2**31 + 977          # seeds may exceed what 32 signed bits hold


def _mix():
    return json.loads((harness.HERE / "traffic" / "chat-steady.json")
                      .read_text())


def _schedule(seed, seconds=30.0):
    online = harness.load_module(harness.HERE / "drivers" / "online.py")
    return online.schedule(_mix(), 152064, seed, seconds)


def test_one_seed_one_schedule():
    a, b = _schedule(BIG), _schedule(BIG)
    assert np.array_equal(a[0], b[0])
    assert a[2] == b[2]
    assert all(np.array_equal(x, y) for x, y in zip(a[1], b[1]))


def test_two_seeds_differ_in_order_not_amount():
    """The pre-roll and the window are drawn apart: each holds the same
    gaps and lengths for every seed, in another order."""
    mix = _mix()
    a, b = _schedule(BIG), _schedule(BIG + 1)
    assert not np.array_equal(a[0], b[0])
    assert a[2] != b[2]
    assert not np.array_equal(a[1][0], b[1][0])
    for lo, hi in ((0.0, mix["preroll_s"]),
                   (mix["preroll_s"], mix["preroll_s"] + 30.0)):
        ia = [i for i, x in enumerate(a[0]) if lo <= x < hi]
        ib = [i for i, x in enumerate(b[0]) if lo <= x < hi]
        assert len(ia) == len(ib) == round(mix["rate_rps"] * (hi - lo))
        assert a[0][ia[0]] == b[0][ib[0]] == lo

        def gaps(at):   # with the closing gap to the end of the span
            return np.sort(np.append(np.diff(at), hi - at[-1]))
        assert np.allclose(gaps(a[0][ia]), gaps(b[0][ib]))
        assert sorted(a[2][i] for i in ia) == sorted(b[2][i] for i in ib)
        assert sorted(len(a[1][i]) for i in ia) == \
            sorted(len(b[1][i]) for i in ib)


def test_stratified_arrivals_fill_the_span_at_the_rate():
    at = gen.stratified_arrivals(4.0, 40.0, BIG)
    assert len(at) == 160
    assert 0.0 <= at[0] and at[-1] < 40.0
    assert np.all(np.diff(at) > 0)


def test_exact_shares():
    assert gen.exact_counts([0.35, 0.30, 0.20, 0.15], 20) == [7, 6, 4, 3]
    assert sum(gen.exact_counts([0.3, 0.3, 0.25, 0.15], 161)) == 161


def test_chat_lengths_keep_their_source_means():
    """The chat mix's shares reproduce the mean prompt and output length
    of the source its file cites, within half a percent."""
    mix = _mix()
    for key in ("prompt_len", "output_len"):
        spec = mix[key]
        assert sum(spec["shares"]) == pytest.approx(1.0, abs=2e-3)
        mean = np.dot(spec["values"], spec["shares"]) / sum(spec["shares"])
        assert mean == pytest.approx(spec["source_mean"], rel=5e-3), key


def test_imdb_copy_matches_the_original():
    from repro.data import imdb_reviews
    a = gen.imdb_reviews(64, 32, 512, 7)
    b = imdb_reviews(n=64, seq_len=32, vocab=512, seed=7)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert gen.percentile(v, 95) == 95.0
    assert gen.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        gen.percentile([], 95)


def test_blocks_spread_the_work_alike_for_every_seed():
    """With ``blocks``, each block of a span holds its share of the
    arrivals and of every length, whatever the seed."""
    mix, n, k = _mix(), 204, 10
    bounds = np.concatenate([[0], np.cumsum(gen.block_counts(n, k))])
    for seed in (BIG, BIG + 1):
        at = gen.stratified_arrivals(4.0, 51.0, seed, "window", k)
        assert len(at) == n and np.all(np.diff(at) > 0) and at[0] == 0.0
        for j in range(k):
            lo, hi = 51.0 * bounds[j] / n, 51.0 * bounds[j + 1] / n
            inside = np.sum((at >= lo - 1e-9) & (at < hi - 1e-9))
            assert inside == bounds[j + 1] - bounds[j]
        outs = np.array([o for _, o in gen.length_pairs(mix, n, seed,
                                                        "window", k)])
        total = gen.exact_counts(mix["output_len"]["shares"], n)
        for j in range(k):
            block = outs[bounds[j]:bounds[j + 1]]
            for v, c in zip(mix["output_len"]["values"], total):
                assert abs(np.sum(block == v) - c / k) < 1.0 + 1e-9
