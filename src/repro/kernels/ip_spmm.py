"""Inner-Product (MNK) SpMSpM Pallas kernel.

TPU realization of the paper's IP dataflow (§3.2.1):

- the K co-iteration walks the *intersection* of A's row fiber and B's
  column fiber, computed at plan time (host) — the TPU analogue of the
  intersection unit: only effectual (k present in both fibers) block pairs
  are ever fetched;
- the intersection lists are already destination-major (i, j, p), so they
  lower directly onto the fused block-run kernel
  (:func:`repro.kernels.stream.stream_spmm`): the C block is stationary in
  a VMEM fp32 accumulator for its whole run and partial sums never leave
  VMEM (no psum/PSRAM traffic — IP's signature property);
- the grid is the *effectual work list*, not ``(Mb, Nb, P)``: empty
  C blocks and the padding waste of the old rectangular grid
  (P − npairs[i,j] idle steps per block) cost zero kernel steps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..config import resolve_interpret
from ..core.dataflows import IPPlan, build_ip_plan
from ..core.formats import BlockCSR, BlockCSC
from .stream import StreamSchedule, schedule_from_ip, stream_spmm

__all__ = ["ip_spmm"]


def ip_spmm(a: BlockCSR, b: BlockCSC, plan: IPPlan | None = None, *,
            schedule: StreamSchedule | None = None, out_dtype=jnp.float32,
            interpret: bool | None = None) -> jax.Array:
    """C = A @ B via the Inner-Product dataflow.  Returns dense C (M, N).

    ``schedule`` (from :func:`repro.kernels.stream.schedule_from_ip`)
    carries the phase-1 work list; omitted, it is rebuilt host-side from
    ``plan`` (which is itself rebuilt from the operand structure when
    omitted).  ``interpret=None`` follows the platform (CPU → interpret).
    """
    interpret = resolve_interpret(interpret)
    if a.nnzb == 0 or b.nnzb == 0:
        return jnp.zeros((a.shape[0], b.shape[1]), out_dtype)
    if schedule is None:
        if plan is None:
            plan = build_ip_plan(a, b)  # lint: host-ok (concrete-only fallback)
        schedule = schedule_from_ip(plan)  # lint: host-ok (concrete-only fallback)
    return stream_spmm(a.data, b.data, schedule,
                       out_grid=(a.grid[0], b.grid[1]),
                       out_shape=(a.shape[0], b.shape[1]),
                       out_dtype=out_dtype, interpret=interpret)
