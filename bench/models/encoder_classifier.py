"""An encoder classifier of the DistilBERT kind: its parameter layout as
the program's tree has it, and the plain float32 reference.

Reference: token plus learned position embeddings, then blocks of
bidirectional multi-head attention (biases on q, k, v and the output)
and a GELU MLP (exact erf form, biases on both matrices), each with a
LayerNorm before it and a residual around it; a final LayerNorm; the
logits are the [CLS] (position 0) state times the label head.

Where this departs from the published DistilBERT (Sanh et al. 2019,
``distilbert-base-uncased``), it follows the program, since the program
states no option for either form: the published model normalizes after
each residual and once after the embeddings, and classifies through a
``pre_classifier`` layer with a ReLU; here the norms come before each
sublayer, a final norm replaces the embedding norm, and the head is one
matrix.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import refops
from weights import Leaf, base_key, draw_leaf

BF16, F32 = "bfloat16", "float32"
NORM_STD = 0.1


def layout(c: dict) -> List[Leaf]:
    d, h, f = c["dim"], c["n_heads"], c["hidden_dim"]
    hd, g = d // h, c["n_layers"]
    v, p, n = c["vocab_size"], c["max_position_embeddings"], c["num_labels"]
    blk = "blocks/0/"

    def st(name, shape, dtype, rule):
        return Leaf(blk + name, (g,) + shape, dtype, rule, True)
    return [
        Leaf("embed", (v, d), BF16, ("normal", 1.0)),
        Leaf("pos_embed", (p, d), BF16, ("normal", 1.0)),
        st("norm1/scale", (d,), F32, ("gain", NORM_STD)),
        st("norm1/bias", (d,), F32, ("normal", NORM_STD)),
        st("attn/wq", (d, h, hd), BF16, ("normal", d ** -0.5)),
        st("attn/wk", (d, h, hd), BF16, ("normal", d ** -0.5)),
        st("attn/wv", (d, h, hd), BF16, ("normal", d ** -0.5)),
        st("attn/wo", (h, hd, d), BF16, ("normal", d ** -0.5)),
        st("attn/bq", (h, hd), BF16, ("normal", NORM_STD)),
        st("attn/bk", (h, hd), BF16, ("normal", NORM_STD)),
        st("attn/bv", (h, hd), BF16, ("normal", NORM_STD)),
        st("attn/bo", (d,), BF16, ("normal", NORM_STD)),
        st("norm2/scale", (d,), F32, ("gain", NORM_STD)),
        st("norm2/bias", (d,), F32, ("normal", NORM_STD)),
        st("mlp/w1", (d, f), BF16, ("normal", d ** -0.5)),
        st("mlp/b1", (f,), BF16, ("normal", NORM_STD)),
        st("mlp/w2", (f, d), BF16, ("normal", f ** -0.5)),
        st("mlp/b2", (d,), BF16, ("normal", NORM_STD)),
        Leaf("final_norm/scale", (d,), F32, ("gain", NORM_STD)),
        Leaf("final_norm/bias", (d,), F32, ("normal", NORM_STD)),
        Leaf("cls_head", (d, n), BF16, ("normal", d ** -0.5)),
    ]


_CONTRACT = {"attn/wq": (0,), "attn/wk": (0,), "attn/wv": (0,),
             "attn/wo": (0, 1), "mlp/w1": (0,), "mlp/w2": (0,)}


def _block(c: dict, w: Dict, x, a=refops.activations(False)):
    """One block over one sequence x (S, D); ``a`` rounds the activation
    operand of each weight product (the control's fp8)."""
    eps = c["layer_norm_eps"]
    h = a(refops.layer_norm(x, w["norm1/scale"], w["norm1/bias"], eps))
    q = jnp.einsum("sd,dhk->shk", h, w["attn/wq"]) + w["attn/bq"]
    k = jnp.einsum("sd,dhk->shk", h, w["attn/wk"]) + w["attn/bk"]
    v = jnp.einsum("sd,dhk->shk", h, w["attn/wv"]) + w["attn/bv"]
    o = a(refops.attention(q, k, v, causal=False), 2)
    x = x + jnp.einsum("shk,hkd->sd", o, w["attn/wo"]) + w["attn/bo"]
    h = a(refops.layer_norm(x, w["norm2/scale"], w["norm2/bias"], eps))
    mlp = a(jax.nn.gelu(h @ w["mlp/w1"] + w["mlp/b1"], approximate=False))
    return x + mlp @ w["mlp/w2"] + w["mlp/b2"]


@functools.lru_cache(maxsize=None)
def _forward(c_items: tuple, quant: bool):
    c = dict(c_items)
    leaves = {l.path: l for l in layout(c)}

    def get(base, path, layer=None):
        w = draw_leaf(base, leaves[path], layer).astype(jnp.float32)
        name = path[len("blocks/0/"):]
        if quant and name in _CONTRACT:
            w = refops.fp8(w, _CONTRACT[name])
        return w

    a = refops.activations(quant)

    def forward(base, tokens):
        s = tokens.shape[1]
        emb = get(base, "embed")
        if quant:
            emb = refops.fp8(emb, (1,))
        x = emb[tokens] + get(base, "pos_embed")[:s]
        for l in range(c["n_layers"]):
            w = {p[len("blocks/0/"):]: get(base, p, l)
                 for p, leaf in leaves.items() if leaf.stacked}
            x = jax.vmap(lambda xi: _block(c, w, xi, a))(x)
        x = refops.layer_norm(x[:, 0], get(base, "final_norm/scale"),
                              get(base, "final_norm/bias"),
                              c["layer_norm_eps"])
        head = get(base, "cls_head")
        if quant:
            head = refops.fp8(head, (0,))
        return a(x) @ head
    return jax.jit(forward)


def _key(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str))))


def logits(c: dict, seed: int, tokens: np.ndarray, *, control: bool = False,
           block_rows: int = 32) -> np.ndarray:
    """(N, num_labels) float32 logits of the reviews ``tokens`` (N, S),
    in blocks of ``block_rows``; with ``control`` in fp8 (weights and
    activations of every weight product)."""
    fwd = _forward(_key(c), control)
    base = base_key(seed)
    n = len(tokens)
    pad = -(-n // block_rows) * block_rows - n
    toks = np.concatenate([tokens, np.zeros((pad, tokens.shape[1]),
                                            tokens.dtype)])
    with jax.default_matmul_precision("highest"):
        out = [np.asarray(fwd(base, jnp.asarray(toks[i:i + block_rows])))
               for i in range(0, len(toks), block_rows)]
    return np.concatenate(out)[:n]
