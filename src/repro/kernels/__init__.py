"""Pallas TPU kernels for the performance hot spots.

- ``ip_spmm`` / ``op_spmm`` / ``gust_spmm`` — the three SpMSpM dataflows on
  one substrate (``stream.py``: a shared :class:`StreamSchedule` work list
  driving two fused streaming kernels, DESIGN.md §18), validated in
  interpret mode.  Plan-level dispatch lives in
  :mod:`repro.backends.pallas` (the ``pallas`` execution backend), which
  builds the phase-1 schedules once per pattern; interpret-mode defaults
  follow the platform through :mod:`repro.config` (CPU → interpret).
- ``moe_gmm.gmm`` — grouped matmul (Gustavson-as-deployed for MoE).
- ``ops.flexagon_spmm`` — deprecated one-shot shim (warns); the plan-once
  entry point is :func:`repro.api.flexagon_plan`.
- ``ref.py`` — pure-jnp oracles.
"""
from .ip_spmm import ip_spmm          # noqa: F401
from .op_spmm import op_spmm          # noqa: F401
from .gust_spmm import gust_spmm      # noqa: F401
from .stream import (  # noqa: F401
    INDEX_MAPS,
    SCHEDULE_KINDS,
    StreamSchedule,
    pad_schedule,
    schedule_from_ip,
    schedule_from_stream,
    stream_panel_spmm,
    stream_spmm,
)
from .moe_gmm import gmm, pad_groups  # noqa: F401
from .ops import flexagon_spmm, spmm_with_dataflow  # noqa: F401
from .ref import spmm_ref, gmm_ref    # noqa: F401
