"""Gustavson (MKN) SpMSpM Pallas kernel.

TPU realization of the paper's Gust dataflow (§3.2.3):

- the **output row panel is stationary**: one ``(bm, N)`` fp32 accumulator
  lives in VMEM for the whole row stripe — GAMMA's fiber-cache / the PSRAM
  row made explicit as scratch;
- **leader-follower intersection**: each nonzero element of A's row fiber
  (the leader) gathers B's entire matching row fiber (the follower); the
  effectual pairs are enumerated at plan time into an i-major work list,
  so no alignment hardware is needed — exactly the paper's argument for
  Gust — and, unlike the old ``(Mb, Amax, Fmax)`` rectangular grid, the
  kernel grid is the work list itself: fiber-length padding costs zero
  steps;
- psums merge *immediately* into the current fiber (accumulate at the
  follower's column offset via
  :func:`repro.kernels.stream.stream_panel_spmm`), so C is written once
  and no psum traffic leaves the chip while a row is in flight.

VMEM bound: ``bm × N × 4`` bytes must fit (for bm=128 that is N ≤ ~64k per
32 MiB of scratch budget); larger N would add an N-tiling level.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..config import resolve_interpret
from ..core.dataflows import StreamPlan, build_gust_plan
from ..core.formats import BlockCSR
from .stream import StreamSchedule, schedule_from_stream, stream_panel_spmm

__all__ = ["gust_spmm"]


def gust_spmm(a: BlockCSR, b: BlockCSR, plan: StreamPlan | None = None, *,
              schedule: StreamSchedule | None = None, out_dtype=jnp.float32,
              interpret: bool | None = None) -> jax.Array:
    """C = A @ B via Gustavson's dataflow.  Returns dense C (M, N).

    ``schedule`` (from :func:`repro.kernels.stream.schedule_from_stream`
    with ``by_dest=False``) carries the phase-1 i-major work list;
    omitted, it is rebuilt host-side from the operand structure.
    ``interpret=None`` follows the platform (CPU → interpret).
    """
    interpret = resolve_interpret(interpret)
    if a.nnzb == 0 or b.nnzb == 0:
        return jnp.zeros((a.shape[0], b.shape[1]), out_dtype)
    if schedule is None:
        if plan is None:
            plan = build_gust_plan(a, b)  # lint: host-ok (concrete-only fallback)
        schedule = schedule_from_stream(plan, by_dest=False)  # lint: host-ok (concrete-only fallback)
    return stream_panel_spmm(a.data, b.data, schedule,
                             out_grid=(a.grid[0], b.grid[1]),
                             out_shape=(a.shape[0], b.shape[1]),
                             out_dtype=out_dtype, interpret=interpret)
