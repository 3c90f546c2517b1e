"""Persistent XLA compile cache for the program's entry points.

A cold process compiles every executable it runs; with the cache on, a
later process with the same programs loads them instead. Entry points
(``repro.launch.serve``, ``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable`; importing ``repro`` does not, so tests never write
compiles into it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache there and
this module sets nothing. Otherwise the cache lives at a fixed path in
the checkout, ``<repo>/.jax_cache`` (git-ignored): the directory is part
of the cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
