"""The one traffic generator: every mix under ``bench/traffic/`` is data
that these functions read.

Copied here from the program on purpose, so that no change to the
program can move the yardstick:

* ``imdb_reviews`` is ``data/synthetic.py``'s IMDb-shaped review set.

``stratified_arrivals`` and ``length_pairs`` give every seed the same
work in another order: the same multiset of inter-arrival gaps (the
exponential's quantiles, so the arrivals are Poisson-shaped) and the
same multiset of lengths (exact shares), each shuffled by the seed. Two
seeds then differ in the order of the work and not in its amount.

With ``blocks`` above 1 the span is cut into that many equal blocks and
each block gets its own share of the arrivals and of every length, so
two seeds also agree on how the load is spread over the span: the seed
orders the work within each block (and decides which blocks take the
few rare lengths).
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, List, Sequence, Tuple

import numpy as np


def key32(seed: int, salt: str = "") -> int:
    """A 31-bit integer drawn from any whole-number seed (``--seed`` may
    exceed what 32 signed bits hold) and a salt naming the use."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), zlib.crc32(
        salt.encode())])
    return int(ss.generate_state(1)[0]) & 0x7FFFFFFF


def rng(seed: int, salt: str) -> np.random.Generator:
    return np.random.default_rng(key32(seed, salt))


# ---------------------------------------------------------------------------
# arrivals
# ---------------------------------------------------------------------------


def block_counts(n: int, blocks: int) -> List[int]:
    """``n`` items over ``blocks`` equal blocks, whole counts."""
    return exact_counts([1.0] * blocks, n)


def stratified_arrivals(rate_rps: float, horizon_s: float, seed: int,
                        salt: str = "", blocks: int = 1) -> np.ndarray:
    """Poisson-shaped arrivals in [0, horizon_s) with the same gaps for
    every seed: ``round(rate * horizon)`` exponential quantiles, in an
    order drawn from the seed (and ``salt``, which names the span). With
    ``blocks`` above 1, each block of the span holds its own share of
    the arrivals, spaced the same way within it."""
    n = int(round(rate_rps * horizon_s))
    if n <= 0:
        return np.asarray([], dtype=np.float64)
    out, start = [], 0.0
    for j, c in enumerate(block_counts(n, blocks)):
        if c == 0:
            continue
        span = horizon_s * c / n
        q = (np.arange(c) + 0.5) / c
        gaps = -np.log1p(-q)
        gaps *= span / gaps.sum()           # c gaps fill the block exactly
        tag = salt + "gaps" + (str(j) if blocks > 1 else "")
        gaps = rng(seed, tag).permutation(gaps)
        out.append(start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]]))
        start += span
    return np.concatenate(out)


# ---------------------------------------------------------------------------
# lengths and prompts
# ---------------------------------------------------------------------------


def exact_counts(shares: Sequence[float], n: int) -> List[int]:
    """Split ``n`` by ``shares`` with whole counts (largest remainder)."""
    shares = np.asarray(shares, np.float64) / float(np.sum(shares))
    raw = shares * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def length_pairs(mix: Dict, n: int, seed: int, salt: str = "",
                 blocks: int = 1) -> List[Tuple[int, int]]:
    """(prompt_len, output_len) for ``n`` requests: each length at its
    exact share, prompts and outputs paired in orders drawn from the
    seed (and ``salt``). With ``blocks`` above 1 the lengths, sorted, are
    dealt over the blocks (``block_counts``) in turn, in an order of the
    blocks drawn from the seed, so every block holds its share of each
    length to within one; then each block is shuffled."""
    cap = np.asarray(block_counts(n, blocks))

    def column(spec, name):
        vals = np.repeat(spec["values"], exact_counts(spec["shares"], n))
        g = rng(seed, salt + name)
        if blocks <= 1:
            return g.permutation(vals)
        # the blocks that hold one more come first in the turn, so the
        # turn fills every block to its count
        big = np.flatnonzero(cap > cap.min())
        turn = np.concatenate([g.permutation(big), g.permutation(
            np.flatnonzero(cap == cap.min()))])
        held = turn[np.arange(n) % blocks]
        srt = np.sort(vals)
        return np.concatenate([g.permutation(srt[held == b])
                               for b in range(blocks)])
    return list(zip(column(mix["prompt_len"], "prompt").tolist(),
                    column(mix["output_len"], "output").tolist()))


def prompt_tokens(n_tokens: int, vocab: int, seed: int, i: int) -> np.ndarray:
    """Request ``i``'s prompt: random ids in [1, vocab)."""
    return rng(seed, f"prompt{i}").integers(1, vocab, size=(n_tokens,),
                                            dtype=np.int32)


# ---------------------------------------------------------------------------
# the offline dataset
# ---------------------------------------------------------------------------


def _zipf_probs(vocab: int, alpha: float = 1.1) -> np.ndarray:
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** (-alpha)
    return p / p.sum()


def imdb_reviews(n: int, seq_len: int, vocab: int, seed: int,
                 signal_frac: float = 0.08) -> Tuple[np.ndarray, np.ndarray]:
    """(tokens (n, seq_len) int32, labels (n,) int32), balanced: Zipfian
    tokens with class-dependent sentiment banks, [CLS] first."""
    r = np.random.default_rng(seed)
    base = _zipf_probs(vocab)
    labels = np.arange(n) % 2
    r.shuffle(labels)
    bank = max(4, vocab // 32)
    start = vocab // 4
    pos_tokens = np.arange(start, start + bank)
    neg_tokens = np.arange(start + bank, start + 2 * bank)
    tokens = r.choice(vocab, size=(n, seq_len), p=base).astype(np.int32)
    n_signal = max(1, int(seq_len * signal_frac))
    for cls, bank in ((1, pos_tokens), (0, neg_tokens)):
        rows = np.where(labels == cls)[0]
        cols = r.integers(1, seq_len, size=(len(rows), n_signal))
        vals = r.choice(bank, size=(len(rows), n_signal))
        tokens[rows[:, None], cols] = vals
    tokens[:, 0] = 101  # [CLS]
    return tokens, labels.astype(np.int32)


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile by the nearest-rank rule (no interpolation):
    the smallest value with at least ``q`` percent of the sample at or
    below it."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of an empty sample")
    k = max(int(math.ceil(q / 100.0 * len(v))) - 1, 0)
    return float(v[k])
