"""The work of the served decode step over a mesh of chips, from shapes.

``bench/work.py`` counts a step's operations and bytes whatever runs
it; here they are set against the whole mesh: its least time is the
larger of the operations over ``n_chips`` bf16 peaks and the bytes over
``n_chips`` HBM bandwidths. The profiler records every executable run
and op once per device, so a trace's step count is its runs over the
devices, and its device time a mean over them.

The sequence-sharded decode attention (``dist/collectives.py``) runs
the ``decode_attention_partials`` kernel on every chip over that chip's
block of the cache. Between them the chips read each live cache
position once; each chip reads every query and writes its float32
partials (``num`` (H, hd), ``den`` and ``m`` (H,) per token) for the
other chips to combine.
"""
from __future__ import annotations

from typing import Tuple

import work

KERNEL = "decode_attention_partials"
_PARTIAL_BYTES = 4          # the kernel's partials are float32


def least_s(flops: float, nbytes: float, device_kind: str,
            n_chips: int) -> float:
    """The least time of work spread over ``n_chips`` chips of one kind:
    the larger of its operations over their bf16 peaks and its bytes
    over their HBM bandwidth."""
    pk = work.peaks(device_kind)
    return max(flops / (n_chips * pk["bf16_flops"]),
               nbytes / (n_chips * pk["hbm_bytes_per_s"]))


def partials_flops(c: dict, sum_ctx: float) -> float:
    """Operations of the partials kernels, all chips together, for
    tokens whose queries see ``sum_ctx`` live positions in all: the two
    products of attention, ``q.k`` and ``p.v``, in every layer."""
    _, h, _, hd, _, layers, _ = work._dec(c)
    return float(4 * layers * h * hd * sum_ctx)


def partials_bytes(c: dict, n_chips: int, n_tokens: int,
                   sum_ctx: float) -> float:
    """Bytes the partials kernels move through HBM, all chips together:
    the live cache positions (each on one chip), and on every chip each
    token's query (in the configuration's dtype) and its partials."""
    _, h, _, hd, _, layers, _ = work._dec(c)
    elem = work._BYTES[c["dtype"]]
    per_token_chip = h * hd * elem + h * (hd + 2) * _PARTIAL_BYTES
    return float(sum_ctx * work.kv_bytes_per_position(c, elem)
                 + layers * n_tokens * n_chips * per_token_chip)


def traced_decode_tokens(rec) -> Tuple[int, float]:
    """The decode tokens the clients received in the traced part of the
    window, and the live positions their queries saw in all (a stream's
    token ``j`` >= 1 was decoded over its prompt and ``j`` tokens)."""
    a, b = rec.facts["traced"]
    ctx = [p + j for x, p, j in rec.facts["tokens"] if j >= 1 and a <= x < b]
    return len(ctx), float(sum(ctx))
