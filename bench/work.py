"""The work a step needs, from shapes: operations and bytes that the
algorithm requires, whatever implements it. Peaks per device kind.

Counted: two operations per multiply-add of every matrix product, and
the two products of attention over the positions each query may see.
Not counted: norms, softmax, activations and bias adds (under 1% here).
Bytes are those a step must move through HBM at least once: every weight
it uses, the live cache positions it reads (not the cache's capacity),
and the cache entries it writes.
"""
from __future__ import annotations

import json
import math
import pathlib
from typing import Dict, Iterable

HERE = pathlib.Path(__file__).resolve().parent
_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def n_params(layout: Iterable) -> int:
    return sum(math.prod(l.shape) for l in layout)


def weight_bytes(layout: Iterable, skip: Iterable[str] = ()) -> int:
    skip = set(skip)
    return sum(math.prod(l.shape) * _BYTES[l.dtype] for l in layout
               if l.path not in skip)


# ---------------------------------------------------------------------------
# encoder classifier (bench/models/encoder_classifier.py)
# ---------------------------------------------------------------------------


def classify_flops(c: dict, seq_len: int) -> float:
    """Operations to classify one review of ``seq_len`` tokens."""
    d, f, layers = c["dim"], c["hidden_dim"], c["n_layers"]
    per_token = 2 * layers * (4 * d * d + 2 * d * f)
    attention = 4 * layers * seq_len * seq_len * d   # q.k and p.v, bidir
    return float(seq_len * per_token + attention
                 + 2 * d * c["num_labels"])


# ---------------------------------------------------------------------------
# decoder LM (bench/models/decoder_lm.py)
# ---------------------------------------------------------------------------


def _dec(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    kv, hd = c["num_key_value_heads"], d // h
    f, v, layers = c["intermediate_size"], c["vocab_size"], \
        c["num_hidden_layers"]
    block = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return d, h, kv, hd, v, layers, block


def kv_bytes_per_position(c: dict, elem_bytes: int = 2) -> int:
    """Key and value bytes of one position over all layers."""
    _, _, kv, hd, _, layers, _ = _dec(c)
    return 2 * layers * kv * hd * elem_bytes


def decode_flops(c: dict, n_tokens: int, sum_ctx: float) -> float:
    """Operations of ``n_tokens`` decoded tokens whose query sees
    ``sum_ctx`` cache positions in all (summed over the tokens)."""
    d, h, _, hd, v, layers, block = _dec(c)
    return float(n_tokens * 2 * (layers * block + d * v)
                 + 4 * layers * h * hd * sum_ctx)


def decode_bytes(c: dict, layout: Iterable, n_steps: int, n_tokens: int,
                 sum_ctx: float) -> float:
    """Bytes of ``n_steps`` decode steps that produced ``n_tokens`` in
    all over ``sum_ctx`` live positions: every weight once a step (of the
    embedding only the rows looked up), the live cache read, and each new
    position written."""
    d = c["hidden_size"]
    w = weight_bytes(layout, skip=("embed",))
    per_pos = kv_bytes_per_position(c)
    return float(n_steps * w + n_tokens * (2 * d + per_pos)
                 + sum_ctx * per_pos)
