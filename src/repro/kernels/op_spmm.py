"""Outer-Product (KMN) SpMSpM Pallas kernel — fused stream + merge.

The paper's OP dataflow (§3.2.2) runs a **streaming phase** producing psum
fibers into the PSRAM, then a **merging phase** combining them row by row
through the MRN.  The TPU realization fuses both phases into one kernel:
the k-major psum work list is **destination-lexsorted at plan time** — the
host sort plays the PSRAM's set/tag lookup — after which the stream arrives
merge-ready and the MRN comparator/adder discipline degenerates to
"accumulate while the destination is unchanged, flush when it moves on"
(block coordinates are dense, so "compare" is "same/different";
DESIGN.md §3/§18).

OP's signature hardware cost — psum traffic between the two phases — is
thereby paid *at plan time* (the sort) instead of at execution time (the
old HBM psum round trip between two ``pallas_call``s): each psum block now
goes straight from the MXU into the VMEM run accumulator.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..config import resolve_interpret
from ..core.dataflows import StreamPlan, build_op_plan
from ..core.formats import BlockCSR, BlockCSC
from .stream import StreamSchedule, schedule_from_stream, stream_spmm

__all__ = ["op_spmm"]


def op_spmm(a: BlockCSC, b: BlockCSR, plan: StreamPlan | None = None, *,
            schedule: StreamSchedule | None = None, out_dtype=jnp.float32,
            interpret: bool | None = None) -> jax.Array:
    """C = A @ B via the Outer-Product dataflow.  Returns dense C (M, N).

    ``schedule`` (from :func:`repro.kernels.stream.schedule_from_stream`
    with ``by_dest=True``) carries the destination-sorted phase-1 work
    list; omitted, it is rebuilt host-side.  ``interpret=None`` follows
    the platform (CPU → interpret).
    """
    interpret = resolve_interpret(interpret)
    if a.nnzb == 0 or b.nnzb == 0:
        return jnp.zeros((a.shape[0], b.shape[1]), out_dtype)
    if schedule is None:
        if plan is None:
            plan = build_op_plan(a, b)  # lint: host-ok (concrete-only fallback)
        schedule = schedule_from_stream(plan, by_dest=True)  # lint: host-ok (concrete-only fallback)
    return stream_spmm(a.data, b.data, schedule,
                       out_grid=(a.grid[0], b.grid[1]),
                       out_shape=(a.shape[0], b.shape[1]),
                       out_dtype=out_dtype, interpret=interpret)
