"""A dense decoder LM of the Qwen2 kind: its parameter layout as the
program's tree has it, and the plain float32 reference.

Reference (Qwen2, arXiv:2407.10671): pre-norm RMSNorm blocks, grouped-
query attention with biases on q, k and v and rotary positions by halves,
a SwiGLU MLP (``w1`` gate, ``w3`` up, ``w2`` down), a final RMSNorm and
an untied output head. The program stores each RMSNorm gain as its
deviation from 1 (gain = 1 + ``scale``); the reference reads it so.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import refops
from weights import Leaf, base_key, draw_leaf

BF16, F32 = "bfloat16", "float32"
NORM_STD = 0.1      # spread of norm gains and biases around 1 and 0


def dims(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return dict(d=d, h=h, kv=c["num_key_value_heads"], hd=d // h,
                f=c["intermediate_size"], v=c["vocab_size"],
                g=c["num_hidden_layers"])


def layout(c: dict) -> List[Leaf]:
    """Every parameter: matrices N(0, 1/fan_in) over the contracted
    dims, so attention scores and activations start at unit scale."""
    m = dims(c)
    d, h, kv, hd, f, v, g = (m[k] for k in ("d", "h", "kv", "hd", "f", "v",
                                            "g"))
    blk = "blocks/0/"

    def st(name, shape, dtype, rule):
        return Leaf(blk + name, (g,) + shape, dtype, rule, True)
    return [
        Leaf("embed", (v, d), BF16, ("normal", 1.0)),
        st("norm1/scale", (d,), F32, ("normal", NORM_STD)),
        st("attn/wq", (d, h, hd), BF16, ("normal", d ** -0.5)),
        st("attn/wk", (d, kv, hd), BF16, ("normal", d ** -0.5)),
        st("attn/wv", (d, kv, hd), BF16, ("normal", d ** -0.5)),
        st("attn/wo", (h, hd, d), BF16, ("normal", (h * hd) ** -0.5)),
        st("attn/bq", (h, hd), BF16, ("normal", NORM_STD)),
        st("attn/bk", (kv, hd), BF16, ("normal", NORM_STD)),
        st("attn/bv", (kv, hd), BF16, ("normal", NORM_STD)),
        st("norm2/scale", (d,), F32, ("normal", NORM_STD)),
        st("mlp/w1", (d, f), BF16, ("normal", d ** -0.5)),
        st("mlp/w3", (d, f), BF16, ("normal", d ** -0.5)),
        st("mlp/w2", (f, d), BF16, ("normal", f ** -0.5)),
        Leaf("final_norm/scale", (d,), F32, ("normal", NORM_STD)),
        Leaf("lm_head", (d, v), BF16, ("normal", d ** -0.5)),
    ]


# contracted axes per matrix, for the fp8 control (layer dim dropped)
_CONTRACT = {"attn/wq": (0,), "attn/wk": (0,), "attn/wv": (0,),
             "attn/wo": (0, 1), "mlp/w1": (0,), "mlp/w3": (0,),
             "mlp/w2": (0,)}


def _layer_weights(c: dict, base, layer, quant: bool) -> Dict:
    out = {}
    for leaf in layout(c):
        if not leaf.stacked:
            continue
        name = leaf.path[len("blocks/0/"):]
        w = draw_leaf(base, leaf, layer).astype(jnp.float32)
        if quant and name in _CONTRACT:
            w = refops.fp8(w, _CONTRACT[name])
        out[name] = w
    return out


def _block(c: dict, w: Dict, x, a=refops.activations(False)):
    """One decoder block over one sequence x (S, D); ``a`` rounds the
    activation operand of each weight product (the control's fp8)."""
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h = a(refops.rms_norm(x, 1.0 + w["norm1/scale"], eps))
    q = jnp.einsum("sd,dhk->shk", h, w["attn/wq"]) + w["attn/bq"]
    k = jnp.einsum("sd,dhk->shk", h, w["attn/wk"]) + w["attn/bk"]
    v = jnp.einsum("sd,dhk->shk", h, w["attn/wv"]) + w["attn/bv"]
    o = refops.attention(refops.rope(q, theta), refops.rope(k, theta), v,
                         causal=True)
    x = x + jnp.einsum("shk,hkd->sd", a(o, 2), w["attn/wo"])
    h = a(refops.rms_norm(x, 1.0 + w["norm2/scale"], eps))
    mlp = a(jax.nn.silu(h @ w["mlp/w1"]) * (h @ w["mlp/w3"]))
    return x + mlp @ w["mlp/w2"]


def _leaf(c: dict, path: str) -> Leaf:
    return next(l for l in layout(c) if l.path == path)


def _key(c: dict) -> tuple:
    """The config's numbers as a hashable key (lists become tuples)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in c.items()
                        if isinstance(v, (int, float, str, list))))


@functools.lru_cache(maxsize=None)
def _jitted(c_items: tuple, quant: bool):
    c = dict(c_items)

    def embed(base, tokens):
        w = draw_leaf(base, _leaf(c, "embed")).astype(jnp.float32)
        if quant:
            w = refops.fp8(w, (1,))
        return w[tokens]

    a = refops.activations(quant)

    def layer(base, l, x):
        w = _layer_weights(c, base, l, quant)
        return jax.vmap(lambda xi: _block(c, w, xi, a))(x)

    def head(base, x_rows):
        g = 1.0 + draw_leaf(base, _leaf(c, "final_norm/scale"))
        lm = draw_leaf(base, _leaf(c, "lm_head")).astype(jnp.float32)
        if quant:
            lm = refops.fp8(lm, (0,))
        return a(refops.rms_norm(x_rows, g, c["rms_norm_eps"])) @ lm
    return (jax.jit(embed), jax.jit(layer), jax.jit(head))


def _hidden(c: dict, seed: int, tokens: np.ndarray, quant: bool,
            block_rows: int):
    """Final-block hidden states (N, S, D), one layer at a time, rows in
    blocks of ``block_rows`` so that the attention scores fit."""
    embed, layer, _ = _jitted(_key(c), quant)
    base = base_key(seed)
    xs = [embed(base, jnp.asarray(tokens[i:i + block_rows]))
          for i in range(0, len(tokens), block_rows)]
    for l in range(c["num_hidden_layers"]):
        xs = [layer(base, np.int32(l), x) for x in xs]
    return jnp.concatenate(xs)


def served_gaps(c: dict, seed: int, seqs: Sequence[np.ndarray],
                n_prompt: Sequence[int], *, control: bool = False,
                block_rows: int = 8) -> List[np.ndarray]:
    """For each sequence (prompt followed by the served tokens), at each
    served position: how far the reference's logit of the served token
    lies below its best logit. With ``control``, the token served is the
    one the fp8 reference ranks first, read against the float32
    reference at the same positions."""
    with jax.default_matmul_precision("highest"):
        s_pad = -(-max(len(t) for t in seqs) // 128) * 128
        toks = np.zeros((len(seqs), s_pad), np.int32)
        for i, t in enumerate(seqs):
            toks[i, :len(t)] = t
        base = base_key(seed)
        _, _, head = _jitted(_key(c), False)
        x = _hidden(c, seed, toks, False, block_rows)
        xq = None
        if control:
            _, _, head_q = _jitted(_key(c), True)
            xq = _hidden(c, seed, toks, True, block_rows)
        out = []
        for i, (t, p) in enumerate(zip(seqs, n_prompt)):
            n = len(t) - p
            rows = np.arange(s_pad) + p - 1      # rows[:n] predict t[p:]
            rows = np.minimum(rows, s_pad - 1)   # one shape for every call
            ref = np.asarray(head(base, x[i, rows]))[:n]
            if control:
                pick = np.asarray(head_q(base, xq[i, rows]))[:n].argmax(-1)
            else:
                pick = np.asarray(t[p:])
            out.append(ref.max(-1) - ref[np.arange(n), pick])
        return out
