"""Activation sharding constraints.

``shard(x, *axes)`` applies ``with_sharding_constraint`` when a mesh set by
``jax.set_mesh`` defines the named axes, and is a no-op otherwise — model
code stays runnable on a bare CPU (smoke tests) and correctly constrained
under the production mesh (dry-run / training).

Convention: ``"dp"`` expands to the data-parallel axes ("pod","data") that
exist on the current mesh.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["shard", "dp_axes"]


def _current_axis_names():
    # `with jax.set_mesh(mesh):` (used around every sharded lowering)
    # installs the mesh this reads
    mesh = jax.sharding.get_abstract_mesh()
    return () if mesh.empty else tuple(mesh.axis_names)


def dp_axes():
    names = _current_axis_names()
    return tuple(a for a in ("pod", "data") if a in names)


def shard(x, *axes):
    """axes: per-dim entries of None, "model", "data", "dp", or tuples."""
    names = _current_axis_names()
    if not names:
        return x
    spec = []
    for a in axes:
        if a == "dp":
            d = dp_axes()
            spec.append(d if d else None)
        elif a is None:
            spec.append(None)
        elif isinstance(a, tuple):
            kept = tuple(ax for ax in a if ax in names)
            spec.append(kept if kept else None)
        else:
            spec.append(a if a in names else None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
