"""Thread-local mesh context + sharding-constraint helpers.

Everything here is mesh-optional: with no active mesh every function
degrades to a no-op / identity, so the same model code runs unmodified on
a single device (unit tests) and under a production mesh (dry-runs,
sharded training). See dist/README.md for the full contract.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_STATE = threading.local()


def _get(name, default=None):
    return getattr(_STATE, name, default)


# ---------------------------------------------------------------------------
# Mesh context
# ---------------------------------------------------------------------------


@contextmanager
def mesh_context(mesh):
    """Install ``mesh`` as the ambient mesh for this thread.

    Nests: the previous mesh (possibly None) is restored on exit, even on
    exception.
    """
    prev = _get("mesh")
    _STATE.mesh = mesh
    try:
        yield mesh
    finally:
        _STATE.mesh = prev


def get_mesh():
    """The ambient mesh, or None outside any ``mesh_context``."""
    return _get("mesh")


def axis_size(name: str, mesh=None) -> int:
    """Size of mesh axis ``name``; 1 if there is no mesh or no such axis."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None or name not in mesh.shape:
        return 1
    return int(mesh.shape[name])


# ---------------------------------------------------------------------------
# Data-parallel axes
# ---------------------------------------------------------------------------


def set_batch_axes(axes: Optional[Sequence[str]]):
    """Override which mesh axes carry the batch (``None`` restores the
    default). Pure-FSDP cells set ("pod", "data", "model") so activations
    batch-shard over every chip; axes absent from the ambient mesh are
    ignored at query time."""
    _STATE.batch_axes = tuple(axes) if axes is not None else None


def dp_axes(mesh=None) -> tuple:
    """The data-parallel (batch) mesh axes, honoring ``set_batch_axes``.

    Default: every mesh axis except "model". Returns () without a mesh.
    """
    mesh = mesh if mesh is not None else get_mesh()
    override = _get("batch_axes")
    if override is not None:
        if mesh is None:
            return tuple(override)
        return tuple(a for a in override if a in mesh.shape)
    if mesh is None:
        return ()
    return tuple(a for a in mesh.axis_names if a != "model")


# ---------------------------------------------------------------------------
# Sharding constraints
# ---------------------------------------------------------------------------


def constrain(x, *axis_names):
    """``with_sharding_constraint(x, P(*axis_names))`` that is safe always:
    no-op without a mesh, and axes that don't exist or don't divide their
    dim are dropped (replicated) rather than erroring."""
    return constrain_dims(x, axis_names)


def constrain_dims(x, spec):
    """Like :func:`constrain` but takes the spec as one sequence whose
    entries may be axis names, tuples of axis names, or None. A spec
    shorter than ``x.ndim`` is padded with None (replicated) dims."""
    mesh = get_mesh()
    if mesh is None:
        return x
    from repro.dist.sharding import sanitize_spec
    s = sanitize_spec(P(*spec), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s))
