"""Weights made by the benchmark from ``--seed``, never by the program.

A model kind (``bench/models/<kind>.py``) states its parameter layout as
a list of ``Leaf``: the path in the program's parameter tree, the shape,
the dtype it is served in, and the rule its values follow. Every leaf
draws from its own key, ``fold_in(seed key, crc32(path))``, and a leaf
stacked over layers draws each layer from ``fold_in(leaf key, layer)``.
So the program's copy (all leaves, in one jitted call on the device)
and the reference's copy (one layer at a time, in float32) are the same
numbers, and neither reads the other.

Rules: ``("normal", std)`` is N(0, std^2); ``("gain", std)`` is
1 + N(0, std^2).
"""
from __future__ import annotations

import zlib
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from gen import key32


class Leaf(NamedTuple):
    path: str                 # "blocks/0/attn/wq"
    shape: Tuple[int, ...]
    dtype: str                # "bfloat16" | "float32"
    rule: Tuple[str, float]
    stacked: bool = False     # leading dim is the layer index


def base_key(seed: int):
    return jax.random.PRNGKey(key32(seed, "weights"))


def _draw(key, shape, rule, dtype):
    kind, std = rule
    x = jax.random.normal(key, shape, jnp.float32) * std
    if kind == "gain":
        x = x + 1.0
    elif kind != "normal":
        raise ValueError(f"unknown weight rule {kind!r}")
    # round to the served dtype, so both copies hold the served numbers
    return x.astype(jnp.dtype(dtype))


def _leaf_key(base, path: str):
    return jax.random.fold_in(base, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw_leaf(base, leaf: Leaf, layer: Optional[int] = None):
    """One leaf (or, for a stacked leaf, one layer of it) as served."""
    k = _leaf_key(base, leaf.path)
    if not leaf.stacked:
        return _draw(k, leaf.shape, leaf.rule, leaf.dtype)
    if layer is not None:
        return _draw(jax.random.fold_in(k, layer), leaf.shape[1:], leaf.rule,
                     leaf.dtype)
    return jnp.stack([_draw(jax.random.fold_in(k, g), leaf.shape[1:],
                            leaf.rule, leaf.dtype)
                      for g in range(leaf.shape[0])])


def nest(flat: Dict[str, object]):
    """{"a/0/b": x} -> {"a": ({"b": x},)}: digit keys become tuples, as
    the program's stacked-block tree has them."""
    root: dict = {}
    for path, val in flat.items():
        node = root
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val

    def fix(node):
        if not isinstance(node, dict):
            return node
        node = {k: fix(v) for k, v in node.items()}
        if node and all(k.isdigit() for k in node):
            return tuple(node[str(i)] for i in range(len(node)))
        return node
    return fix(root)


def flat_paths(tree) -> Dict[str, object]:
    """The inverse of :func:`nest` over any pytree: path -> leaf."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: hasattr(x, "shape"))[0]:
        parts = []
        for p in path:
            parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
        out["/".join(parts)] = leaf
    return out


def check_layout(layout: List[Leaf], program_leaves: Dict[str, object]):
    """Raise unless the program's parameter tree has exactly these paths,
    shapes and dtypes: weights made for another layout would be served
    silently wrong."""
    want = {l.path: (tuple(l.shape), jnp.dtype(l.dtype).name)
            for l in layout}
    got = {p: (tuple(s.shape), jnp.dtype(s.dtype).name)
           for p, s in program_leaves.items()}
    if want != got:
        diff = sorted(set(want.items()) ^ set(got.items()))
        raise ValueError(f"the program's parameter layout differs from the "
                         f"benchmark's: {diff[:6]}")


def program_params(layout: List[Leaf], seed: int, shardings=None):
    """All leaves, nested as the program's tree, made on the device(s) in
    one jitted call (``shardings``: the program's placement, or None)."""
    def make(base):
        return nest({l.path: draw_leaf(base, l) for l in layout})
    return jax.jit(make, out_shardings=shardings)(base_key(seed))
