"""``pallas`` backend — the TPU kernels behind the plan API.

All Pallas dispatch lives here (kernels are imported nowhere else outside
:mod:`repro.kernels` itself):

- :meth:`PallasBackend.prepare` lowers the index plan into the kernels'
  phase-1 artifact — a :class:`repro.kernels.StreamSchedule` work list
  (DESIGN.md §18) — once, at plan time.  Tiles whose effectual block-pair
  count reaches :data:`DENSE_THRESHOLD` of the dense work instead take the
  dense escape hatch (FlexiSAGA, arXiv 2506.01566): a plain MXU matmul on
  the densified operands beats sparse machinery at high occupancy;
- :meth:`PallasBackend.execute` dispatches ``ip_spmm``/``op_spmm``/
  ``gust_spmm``.  N-stationary variants run through the transpose duality
  ``C = (Bᵀ Aᵀ)ᵀ`` with *jnp* transposes (``swapaxes`` on the block data —
  device-side, never a host round trip), against index plans that phase 1
  built for the transposed problem;
- :meth:`PallasBackend.uniform_aux` pads sibling schedules to shared
  extents so stacked sub-plans scan (``scan_streaming``) and shard
  (``collective_merge``) with traced schedule leaves;
- interpret mode resolves in exactly one place: an explicit per-plan
  ``interpret=`` wins, then the backend instance's setting, then the
  platform (:mod:`repro.config`: CPU → interpret).  Compiled (non-interpret)
  execution additionally wants MXU-aligned blocks —
  :meth:`PallasBackend.alignment_diagnostic` surfaces the Mosaic tiling
  rule as a typed ``verify_plan`` diagnostic instead of a compile crash.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import resolve_interpret
from ..core import dataflows as df
from .base import TABLE3_FORMATS, BackendCapability, ExecutionBackend

__all__ = ["DENSE_THRESHOLD", "PallasBackend"]

#: Mosaic tiling for fp32 operands: (sublane, lane) = (8, 128).  Compiled
#: kernels want every 2-D block's second-minor dim a multiple of 8 and its
#: minor dim a multiple of 128; interpret mode has no such constraint.
MXU_SUBLANE = 8
MXU_LANE = 128

#: occupancy escape hatch: when a plan's effectual block-pair count reaches
#: this fraction of the dense block-pair count, :meth:`PallasBackend.prepare`
#: emits a plain dense MXU matmul instead of the sparse kernel.
DENSE_THRESHOLD = 0.5


class PallasBackend(ExecutionBackend):
    name = "pallas"
    # the streaming kernels consume shape-uniform StreamSchedules whose
    # arrays are pytree children, so stacked (traced) sub-plans scan
    # through lax.scan and shard through shard_map + psum
    scan_streaming = True
    collective_merge = True
    # registers every pallas plan with the static schedule checker
    # (repro.analysis.schedule): verify_plan proves the five invariant
    # families over aux["stream_schedule"] before anything executes it
    schedule_aux_key = "stream_schedule"

    def __init__(self, interpret: Optional[bool] = None):
        self.interpret = interpret

    def capabilities(self) -> BackendCapability:
        # All six dataflows (N variants via the transpose duality).  Blocks
        # are unconstrained under interpret mode; compiled TPU runs want
        # MXU-aligned blocks, surfaced as a verify_plan diagnostic
        # (alignment_diagnostic) rather than a block_multiple veto so that
        # interpret-mode plans keep working at any block size.
        return BackendCapability(
            dataflows=tuple(df.DATAFLOWS),
            formats=tuple(set(TABLE3_FORMATS.values())),
            block_multiple=1,
        )

    def _interpret(self, plan) -> bool:
        explicit = plan.interpret if plan.interpret is not None \
            else self.interpret
        return resolve_interpret(explicit)

    # -- phase 1 ---------------------------------------------------------
    def _work_ratio(self, plan) -> float:
        """Effectual block pairs as a fraction of the dense pair count."""
        ip = plan.index_plan
        if hasattr(ip, "npairs"):                      # IPPlan
            w = int(np.asarray(ip.npairs).sum())
        else:                                          # StreamPlan
            w = int(np.asarray(ip.seg_ptr)[-1])
        m, k, n = plan.shapes
        bm, bk, bn = plan.block_shape
        dense = (math.ceil(m / bm) * math.ceil(k / bk) * math.ceil(n / bn))
        return w / max(dense, 1)

    def prepare(self, plan) -> Dict[str, Any]:
        """Lower the index plan to the kernels' streaming work list.

        N-stationary schedules are built for the transposed problem,
        matching how :meth:`execute` runs them.  High-occupancy plans
        additionally carry the dense-escape marker: an aux key with no
        array leaves (``"dense": ()``), so the choice is static under
        tracing and survives sub-plan stacking.
        """
        from ..kernels.stream import schedule_from_ip, schedule_from_stream

        base = plan.dataflow[:-2]
        if base == "ip":
            sched = schedule_from_ip(plan.index_plan)
        elif base == "op":
            sched = schedule_from_stream(plan.index_plan, by_dest=True)
        else:
            sched = schedule_from_stream(plan.index_plan, by_dest=False)
        aux: Dict[str, Any] = {"stream_schedule": sched}
        if self._work_ratio(plan) >= DENSE_THRESHOLD:
            aux["dense"] = ()
        return aux

    def kernel_attrs(self, plan) -> Dict[str, int]:
        """The stream schedule's block pairs, grid steps and runs, and for
        the block-run kernel whether it holds the A block stack in VMEM
        (for float32 blocks; N-stationary plans run with B's blocks as A).
        """
        from ..kernels.stream import a_resident

        sched = plan.aux.get("stream_schedule")
        if sched is None:
            return {}
        attrs = {"block_pairs": sched.n_real_work,
                 "grid_steps": sched.grid_steps, "runs": int(sched.n_runs)}
        if sched.kind == "dest":
            layout = (plan.b_layout if plan.dataflow.endswith("_n")
                      else plan.a_layout)
            bm, bk = layout.block_shape
            attrs["a_resident"] = int(a_resident(layout.nnzb * bm * bk * 4))
        return attrs

    def uniform_aux(self, plans) -> None:
        """Pad sibling schedules to shared (work, run) extents, in place.

        Called at every stacking seam (tiled scan lanes, sharded stacks).
        Also demotes a mixed dense/sparse group to all-sparse: the dense
        marker is treedef-static, so members must agree to stack — and the
        sparse schedule is always present alongside the marker.
        """
        from ..kernels.stream import pad_schedule

        plans = [p for p in plans
                 if isinstance(getattr(p, "aux", None), dict)
                 and "stream_schedule" in p.aux]
        if len(plans) < 2:
            return
        if not all("dense" in p.aux for p in plans):
            for p in plans:
                p.aux.pop("dense", None)
        scheds = [p.aux["stream_schedule"] for p in plans]
        w_max = max(int(np.asarray(s.a_slot).size) for s in scheds)
        r_total = max(s.n_runs for s in scheds) + 1
        for p, s in zip(plans, scheds):
            m, _, n = p.shapes
            bm, _, bn = p.block_shape
            # pad runs scatter one past the *execution-orientation* output
            # grid's row count (the transposed grid for N-stationary)
            oob_row = (math.ceil(n / bn) if p.dataflow.endswith("_n")
                       else math.ceil(m / bm))
            p.aux["stream_schedule"] = pad_schedule(s, w_max, r_total,
                                                    oob_row)

    def alignment_diagnostic(self, plan) -> Optional[str]:
        """MXU/Mosaic block-alignment check for compiled execution.

        Returns a message when ``interpret=False`` resolves for this plan
        and its block shape would crash Mosaic's (8, 128) fp32 tiling, so
        ``verify_plan`` can surface a typed diagnostic at plan time instead
        of a Mosaic internal error at execute time.  ``None`` = fine.
        """
        if self._interpret(plan):
            return None
        bm, bk, bn = plan.block_shape
        bad = []
        if bm % MXU_SUBLANE:
            bad.append(f"bm={bm} % {MXU_SUBLANE} != 0")
        if bk % MXU_LANE:
            bad.append(f"bk={bk} % {MXU_LANE} != 0")
        if bn % MXU_LANE:
            bad.append(f"bn={bn} % {MXU_LANE} != 0")
        if not bad:
            return None
        return ("compiled (interpret=False) pallas execution needs "
                f"MXU-aligned blocks (sublane %{MXU_SUBLANE}, lane "
                f"%{MXU_LANE}); block_shape={tuple(plan.block_shape)} "
                "violates " + ", ".join(bad))

    # -- phase 2 ---------------------------------------------------------
    def _densify(self, x, layout) -> jax.Array:
        """Dense image of a compressed operand via its layout's scatter.

        Safe on padded layouts: padded slots duplicate the (0, 0) block's
        coordinates *and* data, so the duplicate ``.set`` writes agree.
        """
        bm, bk = layout.block_shape
        gr = math.ceil(layout.shape[0] / bm)
        gc = math.ceil(layout.shape[1] / bk)
        canvas = jnp.zeros((gr, gc, bm, bk), x.data.dtype)
        canvas = canvas.at[jnp.asarray(layout.rows, jnp.int32),
                           jnp.asarray(layout.cols, jnp.int32)].set(x.data)
        return canvas.swapaxes(1, 2).reshape(gr * bm, gc * bk)

    def _execute_dense(self, plan, a, b, out_dtype) -> jax.Array:
        m, _, n = plan.shapes
        a_d = self._densify(a, plan.a_layout)
        b_d = self._densify(b, plan.b_layout)
        out = jnp.dot(a_d, b_d, preferred_element_type=jnp.float32)
        return out[:m, :n].astype(out_dtype)

    def execute(self, plan, a, b, out_dtype) -> jax.Array:
        from ..kernels.gust_spmm import gust_spmm
        from ..kernels.ip_spmm import ip_spmm
        from ..kernels.op_spmm import op_spmm

        interpret = self._interpret(plan)
        aux = plan.aux if isinstance(plan.aux, dict) else {}
        if "dense" in aux:
            # occupancy escape hatch: plain dense MXU matmul, orientation-
            # independent (no transpose duality needed)
            return self._execute_dense(plan, a, b, out_dtype)
        sched = aux.get("stream_schedule")  # None -> kernel rebuilds (host)

        base = plan.dataflow[:-2]
        if plan.dataflow.endswith("_n"):
            # transpose duality: C = (Bᵀ Aᵀ)ᵀ — jnp swapaxes only, and the
            # index plan / schedule were built transposed at plan time
            if base == "ip":
                at, bt = df._transpose_bcsc_of(a), df._transpose_bcsr_of(b)
                return ip_spmm(bt, at, plan.index_plan, schedule=sched,
                               out_dtype=out_dtype, interpret=interpret).T
            if base == "op":
                at, bt = df._transpose_bcsr_of(a), df._transpose_bcsc_of(b)
                return op_spmm(bt, at, plan.index_plan, schedule=sched,
                               out_dtype=out_dtype, interpret=interpret).T
            at, bt = df._transpose_bcsr_of(a), df._transpose_bcsr_of(b)
            return gust_spmm(bt, at, plan.index_plan, schedule=sched,
                             out_dtype=out_dtype, interpret=interpret).T
        if base == "ip":
            return ip_spmm(a, b, plan.index_plan, schedule=sched,
                           out_dtype=out_dtype, interpret=interpret)
        if base == "op":
            return op_spmm(a, b, plan.index_plan, schedule=sched,
                           out_dtype=out_dtype, interpret=interpret)
        return gust_spmm(a, b, plan.index_plan, schedule=sched,
                         out_dtype=out_dtype, interpret=interpret)
