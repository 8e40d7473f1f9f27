"""Mesh construction (production, local, and virtual-CPU).

Functions (not module-level constants) so importing this module never
touches jax device state — launchers must set XLA_FLAGS (via
:func:`repro.config.virtual_devices`) before jax's first backend init.

Meshes use ``Auto`` axis types: the models place parameters with
``NamedSharding`` and let the compiler propagate activations, which
``Explicit`` axes (``jax.make_mesh``'s default) would refuse for the
embedding gather.  Enter them with ``jax.set_mesh(mesh)``.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_local_mesh", "make_virtual_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.  Multi-pod adds a pure-DP
    "pod" axis: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh():
    """Whatever devices exist locally, as a (data, model=1) mesh — used by
    smoke tests and the single-host example drivers.

    Degrades gracefully to a (1, 1) mesh on a single-device host (the
    common laptop / CI case), so callers never have to special-case the
    device count.
    """
    n = max(1, len(jax.devices()))
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_virtual_mesh(n: int = 8, axis_name: str = "shards") -> Mesh:
    """A 1-D ``(n,)`` mesh over the first ``n`` local devices.

    The tests/examples entry point for distributed plan execution
    (``flexagon_plan(..., mesh=make_virtual_mesh(8))``): on a CPU host,
    provision virtual devices first with
    :func:`repro.config.virtual_devices` (the test session's conftest does
    this for CI).  ``n=1`` yields a trivial single-shard mesh, mirroring
    :func:`make_local_mesh`'s graceful degradation.
    """
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"make_virtual_mesh({n}) needs {n} devices but only "
            f"{len(devs)} exist; call repro.config.virtual_devices({n}) "
            "before jax initializes its backend")
    return Mesh(np.asarray(devs[:n]), (axis_name,))
