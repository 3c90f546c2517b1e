"""Decomposition + exactly-once merge: the paper's core transformation.

``decompose`` turns a monolithic batch job into parallel chunks (pure
metadata). ``merge`` reassembles per-chunk results in dataset order and
verifies exact coverage — together with the orchestrator's idempotent
commits this gives exactly-once semantics end to end.
"""
from __future__ import annotations

import pickle
from typing import Dict, List

import numpy as np

from repro.core.job import BatchJob, Chunk
from repro.core.store import ArtifactStore
from repro.data.pipeline import chunk_ranges


def decompose(job: BatchJob) -> List[Chunk]:
    ranges = chunk_ranges(job.dataset.n_items, job.batch_size)
    return [Chunk(chunk_id=i, start=s, end=e)
            for i, (s, e) in enumerate(ranges)]


def coverage_ok(chunks: List[Chunk], n_items: int) -> bool:
    """Chunks must partition [0, n_items) exactly: no gap, no overlap."""
    spans = sorted((c.start, c.end) for c in chunks)
    pos = 0
    for s, e in spans:
        if s != pos or e <= s:
            return False
        pos = e
    return pos == n_items


def _result_key(job: BatchJob, chunk_id: int) -> str:
    return f"job/{job.job_id}/result/{chunk_id}"


def commit_result(store: ArtifactStore, job: BatchJob, chunk_id: int,
                  payload) -> bool:
    """Idempotent per-chunk commit: the first writer wins. Returns
    whether this call was that first writer."""
    return store.put(_result_key(job, chunk_id), pickle.dumps(payload),
                     overwrite=False)


def merge(store: ArtifactStore, job: BatchJob,
          chunks: List[Chunk]) -> np.ndarray:
    """Reassemble committed per-chunk predictions in dataset order."""
    out = np.full(job.dataset.n_items, -1, np.int64)
    for c in chunks:
        payload = pickle.loads(store.get(_result_key(job, c.chunk_id)))
        preds = np.asarray(payload["predictions"])
        assert len(preds) == c.n_items, (
            f"chunk {c.chunk_id}: {len(preds)} preds for {c.n_items} items")
        out[c.start:c.end] = preds
    assert (out >= 0).all(), "merge hole: some items have no prediction"
    return out
