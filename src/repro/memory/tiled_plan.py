"""``TiledPlan`` — per-tile :class:`FlexagonPlan`\\ s composed into one apply.

The out-of-core execution engine: when one SpMSpM's pattern exceeds the
:class:`repro.memory.budget.MemoryBudget`, phase 1 partitions it with the
dataflow's :mod:`tile scheduler <repro.memory.tiling>` and builds one
ordinary ``FlexagonPlan`` per tile (same frozen-layout / frozen-index-plan
machinery, same backend ``prepare``).  ``TiledPlan.apply`` then streams the
tiles jit-compatibly:

- disjoint-output tiles (IP C-tiles, Gust row bands) execute and land in
  their output region via static-slice scatter-add;
- OP k-slabs run through **one ``jax.lax.scan``** when the backend declares
  ``scan_streaming``: slab sub-plans are padded to a uniform pytree shape at
  plan time (appended layout slots are never referenced by the frozen work
  lists; padded work entries scatter to an out-of-grid row and are dropped),
  stacked leaf-wise, and the scan carry *is* the cross-slab partial-sum
  merge — the MRN's merge phase lifted to tile granularity
  (:class:`repro.memory.tiling.TileMergePlan` records the regions).

Mixed-dataflow plans (``dataflow="mixed"``, DESIGN.md §14) generalize the
composition: the mixed scheduler tiles on the *output grid* (disjoint C
regions, so per-tile dataflow choices stay merge-compatible), the selection
policy's ``select_tile`` picks each tile's dataflow on the tile's own
occupancy slice, and ``apply`` groups same-dataflow tiles into per-group
lanes — a group whose tiles share one extent streams through its own
``lax.scan`` on scan-capable backends (sub-plans padded/stacked exactly
like OP slabs), the rest unroll.  One jit-compatible ``apply`` either way.

Phase-1 counters behave exactly like the untiled plan: all layout/index-plan
construction happens here at build time; ``apply`` is pure jnp.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..backends import get_backend
from ..backends.base import TABLE3_FORMATS
from ..core import dataflows as df
from ..core.formats import SparseFormat
from ..core.selector import DataflowEstimate, LayerShape, TPUSpec, estimate
from .budget import MemoryBudget
from .tiling import Tile, TileMergePlan, schedule

__all__ = ["TiledPlan", "plan_tiled", "mixed_tile_dataflows"]


def _pack_bitmap(occ: np.ndarray) -> Tuple[bytes, Tuple[int, int]]:
    """Bitmap -> hashable (bytes, shape) so it can ride in the treedef."""
    return np.packbits(occ.astype(bool)).tobytes(), tuple(occ.shape)


def _unpack_bitmap(packed: Tuple[bytes, Tuple[int, int]]) -> np.ndarray:
    buf, shape = packed
    flat = np.unpackbits(np.frombuffer(buf, np.uint8))
    return flat[: shape[0] * shape[1]].reshape(shape).astype(bool)


def _pad_layout(layout, nnzb_max: int):
    """Append never-referenced slots so slab layouts share one shape.

    ``indptr`` keeps the real fiber boundaries, and the frozen work lists
    only index real slots, so the appended (0, 0) coordinates are inert —
    they just make ``compress`` emit a uniformly-shaped data array.
    """
    pad = nnzb_max - layout.nnzb
    if pad == 0:
        return layout
    z = np.zeros(pad, np.int32)
    return dataclasses.replace(
        layout,
        rows=np.concatenate([np.asarray(layout.rows, np.int32), z]),
        cols=np.concatenate([np.asarray(layout.cols, np.int32), z]))


def _pad_stream(plan: df.StreamPlan, w_max: int, oob_row: int
                ) -> df.StreamPlan:
    """Pad a work list to ``w_max`` entries that scatter out of the grid.

    Padded entries gather slot 0 (a real block) but write their psum to
    block-row ``oob_row`` — one past the output grid — which JAX's scatter
    semantics drop.  Numerics are untouched; shapes become uniform.
    """
    pad = w_max - int(plan.a_slot.shape[0])
    if pad == 0:
        return plan
    z = np.zeros(pad, np.int32)
    return df.StreamPlan(
        np.concatenate([np.asarray(plan.a_slot, np.int32), z]),
        np.concatenate([np.asarray(plan.b_slot, np.int32), z]),
        np.concatenate([np.asarray(plan.ci, np.int32),
                        np.full(pad, oob_row, np.int32)]),
        np.concatenate([np.asarray(plan.cj, np.int32), z]),
        plan.seg_ptr, plan.order)


def _pad_ip(plan: df.IPPlan, p_max: int) -> df.IPPlan:
    """Pad an IP intersection plan's pair axis to ``p_max`` slots.

    Appended pairs point at slot 0 but are masked out by ``npairs`` in the
    executor, so numerics are untouched; shapes (and the ``max_pairs``
    treedef entry) become uniform across stacked sub-plans.
    """
    pad = p_max - plan.pair_a.shape[2]
    if pad == 0 and plan.max_pairs == p_max:
        return plan
    wid = ((0, 0), (0, 0), (0, pad))
    return df.IPPlan(np.pad(np.asarray(plan.pair_a, np.int32), wid),
                     np.pad(np.asarray(plan.pair_b, np.int32), wid),
                     np.asarray(plan.npairs, np.int32), p_max)


def _stack_plans(plans):
    """Stack uniform slab plans leaf-wise (phase-1 work, done once).

    Guards uniformity up front: every member must flatten to the same
    treedef (same aux, e.g. ``StreamSchedule`` ``(n_runs, kind)``) and the
    matching leaves must share shapes, otherwise ``jnp.stack`` would fail
    deep inside ``tree_map`` with an opaque error.  The static schedule
    checker (``repro.analysis.schedule.check_stack_uniform``) catches the
    same mismatch at verify time; this is the build-time backstop.
    """
    leaves0, treedef0 = jax.tree_util.tree_flatten(plans[0])
    shapes0 = [getattr(x, "shape", ()) for x in leaves0]
    for i, p in enumerate(plans[1:], start=1):
        leaves, treedef = jax.tree_util.tree_flatten(p)
        if treedef != treedef0:
            raise ValueError(
                f"_stack_plans: member {i} has a different pytree structure "
                f"than member 0 (e.g. mismatched schedule kind/n_runs aux); "
                f"got {treedef} vs {treedef0}")
        shapes = [getattr(x, "shape", ()) for x in leaves]
        if shapes != shapes0:
            bad = next((j, shapes[j], shapes0[j])
                       for j in range(len(shapes)) if shapes[j] != shapes0[j])
            raise ValueError(
                f"_stack_plans: member {i} leaf {bad[0]} has shape {bad[1]} "
                f"but member 0 has {bad[2]}; slab plans must be uniform to "
                f"stack for the scan path")
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *plans)


def _build_sub_plan(dataflow: str, occ_at: np.ndarray, occ_bt: np.ndarray,
                    block_shape: Tuple[int, int, int], backend,
                    fingerprint: str, interpret: Optional[bool],
                    spec: TPUSpec, est: Optional[DataflowEstimate] = None):
    """One tile/shard sub-``FlexagonPlan`` on an occupancy slice (phase 1).

    The single construction path for every sub-plan of a tiled, mixed, or
    sharded plan: layouts from the slice bitmaps, the dataflow's index
    plan, and a per-slice estimate unless the caller supplies a shared one
    (stack-uniform treedefs).  ``aux`` is left for the caller's
    ``backend.prepare`` pass — lanes pad layouts first.
    """
    from ..api import CompressionLayout, FlexagonPlan, _build_index_plan

    bm, bk, bn = block_shape
    fmt_a, fmt_b = TABLE3_FORMATS[dataflow]
    shape_a = (occ_at.shape[0] * bm, occ_at.shape[1] * bk)
    shape_b = (occ_bt.shape[0] * bk, occ_bt.shape[1] * bn)
    a_layout = CompressionLayout.from_bitmap(occ_at, shape_a, (bm, bk),
                                             fmt_a)
    b_layout = CompressionLayout.from_bitmap(occ_bt, shape_b, (bk, bn),
                                             fmt_b)
    index_plan = _build_index_plan(dataflow, a_layout, b_layout)
    if est is None:
        est = estimate(
            LayerShape(m=shape_a[0], k=shape_a[1], n=shape_b[1],
                       density_a=float(occ_at.mean()) if occ_at.size else 0.0,
                       density_b=float(occ_bt.mean()) if occ_bt.size else 0.0,
                       block=tuple(block_shape)), dataflow, spec)
    return FlexagonPlan(
        dataflow=dataflow, a_layout=a_layout, b_layout=b_layout,
        index_plan=index_plan, aux=None, estimate=est,
        fingerprint=fingerprint,
        shapes=(shape_a[0], shape_a[1], shape_b[1]),
        block_shape=tuple(block_shape), backend=backend.name,
        interpret=interpret)


def mixed_tile_dataflows(occ_a: np.ndarray, occ_b: np.ndarray,
                         block_shape: Tuple[int, int, int],
                         budget: MemoryBudget, *, backend, policy=None,
                         spec: TPUSpec = TPUSpec(), fingerprint: str = "",
                         tiles: Optional[List[Tile]] = None
                         ) -> Tuple[str, ...]:
    """Per-tile dataflow choices for one ``"mixed"`` schedule (phase 1).

    Evaluates the selection policy's ``select_tile`` on every tile's own
    occupancy slice.  Deterministic for a fixed (pattern, budget, policy,
    backend) — :class:`repro.api.PlanCache` keys mixed plans under exactly
    this tuple, so two policies that agree tile-by-tile share one plan.
    """
    from ..backends.base import allowed_dataflows
    from ..backends.policies import SelectionContext, get_policy

    backend = get_backend(backend)
    policy = get_policy(policy, "mixed")
    if tiles is None:
        tiles, _ = schedule("mixed", occ_a, occ_b, block_shape, budget)
    allowed = allowed_dataflows(backend, tuple(block_shape))
    if not allowed:
        raise ValueError(f"backend {backend.name!r} supports no dataflow "
                         f"at block_shape={tuple(block_shape)}")
    bm, bk, bn = block_shape
    choices = []
    for idx, tile in enumerate(tiles):
        occ_at = tile.a_slice(occ_a)
        occ_bt = tile.b_slice(occ_b)
        shape = LayerShape(
            m=(tile.i1 - tile.i0) * bm, k=(tile.k1 - tile.k0) * bk,
            n=(tile.j1 - tile.j0) * bn,
            density_a=float(occ_at.mean()) if occ_at.size else 0.0,
            density_b=float(occ_bt.mean()) if occ_bt.size else 0.0,
            block=tuple(block_shape))
        ctx = SelectionContext(
            shape=shape, block_shape=tuple(block_shape), occ_a=occ_at,
            occ_b=occ_bt, fingerprint=f"{fingerprint}/tile{idx}",
            backend=backend, spec=spec, allowed=allowed, tile=tile)
        t_sel = obs.now_ns()
        with obs.span("plan.select_tile", tile=idx,
                      policy=type(policy).__name__):
            choices.append(policy.select_tile(ctx))
        obs.get_registry().histogram("policy.select_tile_s").observe(
            (obs.now_ns() - t_sel) / 1e9)
    return tuple(choices)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TiledPlan:
    """Phase-1 output for one SpMSpM that does not fit on chip.

    Mirrors the :class:`repro.api.FlexagonPlan` surface (``apply`` /
    ``__call__`` / ``dataflow`` / ``out_major`` / ``matches`` /
    ``with_backend`` / ``pack_a`` / ``pack_b``) so callers can hold either.
    ``plans`` are ordinary per-tile ``FlexagonPlan``\\ s; ``tiles`` and
    ``merge_plan`` are the static schedule; the operand bitmaps ride packed
    in the treedef so traffic reports survive pytree round trips.
    """

    dataflow: str                            # a dataflow name, or "mixed"
    tiles: Tuple[Tile, ...]
    merge_plan: TileMergePlan
    plans: Tuple[Any, ...]                   # per-tile FlexagonPlans (children)
    shapes: Tuple[int, int, int]
    block_shape: Tuple[int, int, int]
    backend: str
    budget: MemoryBudget
    fingerprint: str
    interpret: Optional[bool]
    scan_ok: bool                            # OP slabs uniform & non-empty
    occ_a_packed: Tuple[bytes, Tuple[int, int]]
    occ_b_packed: Tuple[bytes, Tuple[int, int]]
    #: slab plans stacked leaf-wise for the scan path, built once at plan
    #: time (phase 1) so every eager ``apply`` skips the restack
    scan_stacked: Any = None
    #: dataflow executed by each tile; ``(dataflow,) * n_tiles`` for
    #: single-dataflow plans, the policy's per-tile choices for "mixed"
    tile_dataflows: Tuple[str, ...] = ()
    #: mixed scan lanes: ((dataflow, tile_indices), ...) per group whose
    #: sub-plans were padded to one pytree shape (static schedule, aux)
    scan_group_meta: Tuple[Tuple[str, Tuple[int, ...]], ...] = ()
    #: the stacked sub-plan pytree of each scan lane, aligned with
    #: ``scan_group_meta`` (children; built once at plan time)
    scan_group_stacks: Tuple[Any, ...] = ()

    # -- pytree plumbing -------------------------------------------------
    def tree_flatten(self):
        aux = (self.dataflow, self.tiles, self.merge_plan, self.shapes,
               self.block_shape, self.backend, self.budget, self.fingerprint,
               self.interpret, self.scan_ok, self.occ_a_packed,
               self.occ_b_packed, self.tile_dataflows, self.scan_group_meta)
        return ((tuple(self.plans), self.scan_stacked,
                 tuple(self.scan_group_stacks)), aux)

    @classmethod
    def tree_unflatten(cls, aux, children):
        plans, scan_stacked, scan_group_stacks = children
        (dataflow, tiles, merge_plan, shapes, block_shape, backend, budget,
         fingerprint, interpret, scan_ok, occ_a, occ_b, tile_dataflows,
         scan_group_meta) = aux
        return cls(dataflow, tiles, merge_plan, tuple(plans), shapes,
                   block_shape, backend, budget, fingerprint, interpret,
                   scan_ok, occ_a, occ_b, scan_stacked, tile_dataflows,
                   scan_group_meta, tuple(scan_group_stacks))

    def __post_init__(self):
        if not self.tile_dataflows:
            self.tile_dataflows = (self.dataflow,) * len(self.tiles)

    # -- phase-1 byproducts ----------------------------------------------
    @property
    def n_tiles(self) -> int:
        return len(self.tiles)

    @property
    def is_mixed(self) -> bool:
        return self.dataflow == "mixed"

    @property
    def tile_histogram(self) -> Dict[str, int]:
        """How many tiles run each dataflow (the "mixed" telemetry view)."""
        return dict(Counter(self.tile_dataflows))

    @property
    def groups(self) -> Dict[str, Tuple[int, ...]]:
        """Tile indices per dataflow, in execution order."""
        out: Dict[str, List[int]] = {}
        for i, d in enumerate(self.tile_dataflows):
            out.setdefault(d, []).append(i)
        return {d: tuple(v) for d, v in out.items()}

    @property
    def out_major(self) -> str:
        # mixed tiles assemble a dense C from disjoint regions; report the
        # row-major default that every Table 4 transition can ingest
        if self.is_mixed:
            return "csr"
        return df.OUTPUT_MAJOR[self.dataflow]

    @property
    def formats(self):
        # packing is a storage convenience for tiled plans (apply densifies
        # before slicing), so mixed plans default to row-major block storage
        if self.is_mixed:
            return (SparseFormat.BCSR, SparseFormat.BCSR)
        return TABLE3_FORMATS[self.dataflow]

    @property
    def use_pallas(self) -> bool:
        return self.backend == "pallas"

    @property
    def occ_a(self) -> np.ndarray:
        return _unpack_bitmap(self.occ_a_packed)

    @property
    def occ_b(self) -> np.ndarray:
        return _unpack_bitmap(self.occ_b_packed)

    @property
    def estimate(self) -> DataflowEstimate:
        """Aggregate over tiles (re-reads across tiles count once per tile)."""
        ests = [p.estimate for p in self.plans]
        return DataflowEstimate(
            dataflow=self.dataflow,
            flops=sum(e.flops for e in ests),
            bytes_a=sum(e.bytes_a for e in ests),
            bytes_b=sum(e.bytes_b for e in ests),
            bytes_c=sum(e.bytes_c for e in ests),
            bytes_psum=sum(e.bytes_psum for e in ests),
            compute_s=sum(e.compute_s for e in ests),
            memory_s=sum(e.memory_s for e in ests),
        )

    def matches(self, a, b) -> bool:
        """Do these operands carry the planned (whole-operation) pattern?"""
        from ..api import _fingerprint, _pattern_of

        (m, k), occ_a = _pattern_of(a, self.block_shape[:2])
        (_, n), occ_b = _pattern_of(b, self.block_shape[1:])
        return _fingerprint(occ_a, occ_b, (m, k, n),
                            self.block_shape) == self.fingerprint

    def with_backend(self, backend) -> "TiledPlan":
        """Re-target onto another backend.

        Backends that stream slabs through ``lax.scan`` carry padded slab
        plans; re-targeting to a non-scanning backend (or vice versa)
        re-tiles from the stored bitmaps so each substrate gets the plan
        shape it expects.  Mixed plans always rebuild — with the per-tile
        choices *pinned*, so re-targeting never re-runs the policy.
        """
        be = get_backend(backend)
        if self.is_mixed:
            return plan_tiled(
                dataflow="mixed", occ_a=self.occ_a, occ_b=self.occ_b,
                shapes=self.shapes, block_shape=self.block_shape,
                budget=self.budget, backend=be, interpret=self.interpret,
                fingerprint=self.fingerprint,
                tile_dataflows=self.tile_dataflows)
        if self.scan_ok != (self.dataflow[:-2] == "op" and be.scan_streaming):
            return plan_tiled(
                dataflow=self.dataflow, occ_a=self.occ_a, occ_b=self.occ_b,
                shapes=self.shapes, block_shape=self.block_shape,
                budget=self.budget, backend=be, interpret=self.interpret,
                fingerprint=self.fingerprint)
        plans = tuple(p.with_backend(be) for p in self.plans)
        if self.scan_ok:
            # re-preparing per plan makes aux non-uniform again; re-pad
            # before restacking the slab axis
            be.uniform_aux(list(plans))
        return dataclasses.replace(
            self, backend=be.name, plans=plans,
            scan_stacked=_stack_plans(plans) if self.scan_ok else None)

    # -- packing (host-side conveniences, phase-1 style) ------------------
    def _pack(self, x, fmt, block_shape):
        from ..api import SparseOperand

        if isinstance(x, SparseOperand):
            x = np.asarray(x.todense())
        return SparseOperand.from_dense(np.asarray(x), format=fmt,
                                        block_shape=block_shape)

    def pack_a(self, a):
        """Whole-operand compression in the planned A format.

        Tiles ingest dense slices, so packing is a storage convenience here
        (``apply`` densifies packed operands before slicing)."""
        return self._pack(a, self.formats[0], self.block_shape[:2])

    def pack_b(self, b):
        return self._pack(b, self.formats[1], self.block_shape[1:])

    # -- phase 2 ---------------------------------------------------------
    def _densify(self, x) -> jax.Array:
        from ..api import SparseOperand

        if isinstance(x, SparseOperand):
            return x.todense()
        if hasattr(x, "todense") and not isinstance(x, (np.ndarray,
                                                        jax.Array)):
            return x.todense()
        return jnp.asarray(x)

    def apply(self, a, b, out_dtype=jnp.float32) -> jax.Array:
        """Execute C = A @ B tile by tile.  jit-compatible, zero host work;
        the device work is named ``memory.tiled.apply`` in a trace."""
        with jax.named_scope("memory.tiled.apply"):
            return self._apply_inner(a, b, out_dtype)

    def _apply_inner(self, a, b, out_dtype=jnp.float32) -> jax.Array:
        m, k, n = self.shapes
        bm, bk, bn = self.block_shape
        mb = max(t.i1 for t in self.tiles)
        kb = max(t.k1 for t in self.tiles)
        nb = max(t.j1 for t in self.tiles)
        a_d = self._densify(a).astype(jnp.float32)
        b_d = self._densify(b).astype(jnp.float32)
        a_d = jnp.pad(a_d, ((0, mb * bm - a_d.shape[0]),
                            (0, kb * bk - a_d.shape[1])))
        b_d = jnp.pad(b_d, ((0, kb * bk - b_d.shape[0]),
                            (0, nb * bn - b_d.shape[1])))

        backend = get_backend(self.backend)
        if self.is_mixed and self.scan_group_meta:
            out = self._apply_mixed(a_d, b_d)
        elif self.scan_ok and backend.scan_streaming:
            out = self._apply_scan(a_d, b_d)
        else:
            out = jnp.zeros((mb * bm, nb * bn), jnp.float32)
            for tile, plan in zip(self.tiles, self.plans):
                a_s = a_d[tile.i0 * bm: tile.i1 * bm,
                          tile.k0 * bk: tile.k1 * bk]
                b_s = b_d[tile.k0 * bk: tile.k1 * bk,
                          tile.j0 * bn: tile.j1 * bn]
                t_out = plan.apply(a_s, b_s, jnp.float32)
                out = out.at[tile.i0 * bm: tile.i1 * bm,
                             tile.j0 * bn: tile.j1 * bn].add(t_out)
        return out[:m, :n].astype(out_dtype)

    __call__ = apply

    def _apply_mixed(self, a_d: jax.Array, b_d: jax.Array) -> jax.Array:
        """Per-group lanes for heterogeneous tiles (DESIGN.md §14).

        Every scan lane streams its same-dataflow, same-extent tiles through
        one ``lax.scan`` (the OP-slab machinery generalized): the carry is
        the output canvas, each step dynamic-slices the tile's operand
        stripes, runs the tile sub-plan, and writes the disjoint C region in
        place (disjoint ⇒ set == add).  Tiles outside any lane unroll with
        the static-slice scatter-add below.
        """
        bm, bk, bn = self.block_shape
        out = jnp.zeros((a_d.shape[0], b_d.shape[1]), jnp.float32)
        in_lane = set()
        for (d, idxs), stacked in zip(self.scan_group_meta,
                                      self.scan_group_stacks):
            in_lane.update(idxs)
            lane_tiles = [self.tiles[i] for i in idxs]
            h = (lane_tiles[0].i1 - lane_tiles[0].i0) * bm
            w = (lane_tiles[0].j1 - lane_tiles[0].j0) * bn
            oi = jnp.asarray([t.i0 * bm for t in lane_tiles], jnp.int32)
            oj = jnp.asarray([t.j0 * bn for t in lane_tiles], jnp.int32)

            def body(carry, xs, h=h, w=w):
                sub, o_i, o_j = xs
                a_s = jax.lax.dynamic_slice(a_d, (o_i, 0), (h, a_d.shape[1]))
                b_s = jax.lax.dynamic_slice(b_d, (0, o_j), (b_d.shape[0], w))
                t_out = sub.apply(a_s, b_s, jnp.float32)
                return (jax.lax.dynamic_update_slice(carry, t_out,
                                                     (o_i, o_j)), None)

            out, _ = jax.lax.scan(body, out, (stacked, oi, oj))
        for i, (tile, plan) in enumerate(zip(self.tiles, self.plans)):
            if i in in_lane:
                continue
            a_s = a_d[tile.i0 * bm: tile.i1 * bm,
                      tile.k0 * bk: tile.k1 * bk]
            b_s = b_d[tile.k0 * bk: tile.k1 * bk,
                      tile.j0 * bn: tile.j1 * bn]
            t_out = plan.apply(a_s, b_s, jnp.float32)
            out = out.at[tile.i0 * bm: tile.i1 * bm,
                         tile.j0 * bn: tile.j1 * bn].add(t_out)
        return out

    def _apply_scan(self, a_d: jax.Array, b_d: jax.Array) -> jax.Array:
        """OP k-slabs through one ``lax.scan``: the carry accumulates the
        cross-slab partial sums (double-buffer-style streaming — XLA keeps
        slab s+1's loads in flight while slab s multiplies)."""
        bm, bk, bn = self.block_shape
        s = len(self.plans)
        ke = self.tiles[0].k1 - self.tiles[0].k0
        stacked = self.scan_stacked
        if stacked is None:            # e.g. plan rebuilt by hand
            stacked = _stack_plans(self.plans)
        a_slabs = a_d.reshape(a_d.shape[0], s, ke * bk).transpose(1, 0, 2)
        b_slabs = b_d.reshape(s, ke * bk, b_d.shape[1])

        def body(carry, xs):
            plan, a_i, b_i = xs
            return carry + plan.apply(a_i, b_i, jnp.float32), None

        init = jnp.zeros((a_d.shape[0], b_d.shape[1]), jnp.float32)
        out, _ = jax.lax.scan(body, init, (stacked, a_slabs, b_slabs))
        return out


def plan_tiled(*, dataflow: str, occ_a: np.ndarray, occ_b: np.ndarray,
               shapes: Tuple[int, int, int],
               block_shape: Tuple[int, int, int],
               budget: MemoryBudget, backend, interpret: Optional[bool],
               fingerprint: str, spec: TPUSpec = TPUSpec(),
               policy=None,
               tile_dataflows: Optional[Tuple[str, ...]] = None
               ) -> Optional[TiledPlan]:
    """Phase 1 for the out-of-core case.

    Returns ``None`` when the scheduler covers the operation with a single
    budget-fitting tile (the caller then builds an ordinary untiled plan).
    ``dataflow="mixed"`` routes to the heterogeneous planner: ``policy``
    prices each tile (``select_tile``), or ``tile_dataflows`` pins the
    per-tile choices outright (re-targeting, reproducibility).
    """
    if dataflow == "mixed":
        return _plan_mixed(occ_a=occ_a, occ_b=occ_b, shapes=shapes,
                           block_shape=block_shape, budget=budget,
                           backend=backend, interpret=interpret,
                           fingerprint=fingerprint, spec=spec, policy=policy,
                           tile_dataflows=tile_dataflows)

    with obs.span("plan.schedule", dataflow=dataflow) as _sp:
        tiles, merge_plan = schedule(dataflow, occ_a, occ_b, block_shape,
                                     budget)
        _sp.set(tiles=len(tiles))
    if len(tiles) <= 1:
        return None

    m, k, n = shapes
    bm, bk, bn = block_shape
    base = dataflow[:-2]
    scan_capable = base == "op" and backend.scan_streaming

    # pad the bitmap grids out to the tile extents (OP's uniform slabs may
    # run past the logical K grid; the padding is empty fibers)
    mb = max(t.i1 for t in tiles)
    kb = max(t.k1 for t in tiles)
    nb = max(t.j1 for t in tiles)
    occ_a_p = np.zeros((mb, kb), dtype=bool)
    occ_a_p[: occ_a.shape[0], : occ_a.shape[1]] = occ_a
    occ_b_p = np.zeros((kb, nb), dtype=bool)
    occ_b_p[: occ_b.shape[0], : occ_b.shape[1]] = occ_b

    shared_est = None
    if scan_capable:
        # slab plans must share one treedef to stack into the scan; give
        # them one fingerprint and one (slab-shaped) estimate
        ke = tiles[0].k1 - tiles[0].k0
        shared_est = estimate(
            LayerShape(m=mb * bm, k=ke * bk, n=nb * bn,
                       density_a=float(occ_a.mean()) if occ_a.size else 0.0,
                       density_b=float(occ_b.mean()) if occ_b.size else 0.0,
                       block=tuple(block_shape)), dataflow, spec)

    plans: List[Any] = []
    for idx, tile in enumerate(tiles):
        fp = f"{fingerprint}/opslab" if scan_capable \
            else f"{fingerprint}/t{idx}"
        plans.append(_build_sub_plan(
            dataflow, tile.a_slice(occ_a_p), tile.b_slice(occ_b_p),
            tuple(block_shape), backend, fp, interpret, spec,
            est=shared_est))

    scan_ok = False
    if scan_capable:
        nnz_a = max(p.a_layout.nnzb for p in plans)
        nnz_b = max(p.b_layout.nnzb for p in plans)
        w_max = max(int(p.index_plan.a_slot.shape[0]) for p in plans)
        oob_row = nb if dataflow.endswith("_n") else mb   # transposed grid
        for p in plans:
            p.a_layout = _pad_layout(p.a_layout, nnz_a)
            p.b_layout = _pad_layout(p.b_layout, nnz_b)
            p.index_plan = _pad_stream(p.index_plan, w_max, oob_row)
        scan_ok = w_max > 0

    for p in plans:
        p.aux = backend.prepare(p)
    if scan_ok:
        # backend aux schedules must stack too (shape-uniform across slabs)
        backend.uniform_aux(plans)

    return TiledPlan(
        dataflow=dataflow, tiles=tuple(tiles), merge_plan=merge_plan,
        plans=tuple(plans), shapes=tuple(shapes),
        block_shape=tuple(block_shape), backend=backend.name, budget=budget,
        fingerprint=fingerprint, interpret=interpret, scan_ok=scan_ok,
        occ_a_packed=_pack_bitmap(occ_a), occ_b_packed=_pack_bitmap(occ_b),
        scan_stacked=_stack_plans(plans) if scan_ok else None)


def _plan_mixed(*, occ_a: np.ndarray, occ_b: np.ndarray,
                shapes: Tuple[int, int, int],
                block_shape: Tuple[int, int, int], budget: MemoryBudget,
                backend, interpret: Optional[bool], fingerprint: str,
                spec: TPUSpec, policy,
                tile_dataflows: Optional[Tuple[str, ...]]
                ) -> Optional[TiledPlan]:
    """Phase 1 for heterogeneous per-tile dataflows (DESIGN.md §14).

    The mixed scheduler tiles the output grid (disjoint C regions, full K
    per tile), the policy's ``select_tile`` picks each tile's dataflow on
    the tile's own occupancy slice, and same-dataflow tiles are grouped into
    lanes: a group whose tiles share one extent is padded/stacked into a
    ``lax.scan`` lane on scan-capable backends (the OP-slab machinery),
    everything else unrolls.  Returns ``None`` for a single-tile schedule —
    there is nothing to mix, the caller degenerates to a policy-chosen
    single-dataflow plan.
    """
    with obs.span("plan.schedule", dataflow="mixed") as _sp:
        tiles, merge_plan = schedule("mixed", occ_a, occ_b, block_shape,
                                     budget)
        _sp.set(tiles=len(tiles))
    if len(tiles) <= 1:
        return None
    if tile_dataflows is None:
        tile_dataflows = mixed_tile_dataflows(
            occ_a, occ_b, block_shape, budget, backend=backend,
            policy=policy, spec=spec, fingerprint=fingerprint, tiles=tiles)
    if len(tile_dataflows) != len(tiles):
        raise ValueError(f"got {len(tile_dataflows)} per-tile dataflows for "
                         f"{len(tiles)} tiles")

    bm, bk, bn = block_shape
    groups: Dict[str, List[int]] = {}
    for idx, d in enumerate(tile_dataflows):
        groups.setdefault(d, []).append(idx)

    plans: List[Any] = [None] * len(tiles)
    scan_group_meta: List[Tuple[str, Tuple[int, ...]]] = []
    scan_group_stacks: List[Any] = []
    for d, idxs in groups.items():
        extents = {(tiles[i].i1 - tiles[i].i0, tiles[i].j1 - tiles[i].j0)
                   for i in idxs}
        lane = backend.scan_streaming and len(idxs) > 1 and len(extents) == 1
        shared_est = None
        if lane:
            # lane sub-plans must share one treedef to stack: one
            # (group-uniform) estimate and one fingerprint, like OP slabs
            t0 = tiles[idxs[0]]
            shared_est = estimate(
                LayerShape(
                    m=(t0.i1 - t0.i0) * bm, k=(t0.k1 - t0.k0) * bk,
                    n=(t0.j1 - t0.j0) * bn,
                    density_a=float(occ_a.mean()) if occ_a.size else 0.0,
                    density_b=float(occ_b.mean()) if occ_b.size else 0.0,
                    block=tuple(block_shape)), d, spec)
        group_plans: List[Any] = []
        for i in idxs:
            tile = tiles[i]
            fp = f"{fingerprint}/mixed/{d}" if lane \
                else f"{fingerprint}/t{i}"
            group_plans.append(_build_sub_plan(
                d, tile.a_slice(occ_a), tile.b_slice(occ_b),
                tuple(block_shape), backend, fp, interpret, spec,
                est=shared_est))
        if lane:
            nnz_a = max(p.a_layout.nnzb for p in group_plans)
            nnz_b = max(p.b_layout.nnzb for p in group_plans)
            for p in group_plans:
                p.a_layout = _pad_layout(p.a_layout, nnz_a)
                p.b_layout = _pad_layout(p.b_layout, nnz_b)
            if isinstance(group_plans[0].index_plan, df.IPPlan):
                p_max = max(int(p.index_plan.pair_a.shape[2])
                            for p in group_plans)
                for p in group_plans:
                    p.index_plan = _pad_ip(p.index_plan, p_max)
            else:
                w_max = max(int(p.index_plan.a_slot.shape[0])
                            for p in group_plans)
                t0 = tiles[idxs[0]]
                # N-stationary executors scatter on the transposed grid
                oob = (t0.j1 - t0.j0) if d.endswith("_n") \
                    else (t0.i1 - t0.i0)
                for p in group_plans:
                    p.index_plan = _pad_stream(p.index_plan, w_max, oob)
                lane = w_max > 0          # all-empty lane: just unroll it
        for p in group_plans:
            p.aux = backend.prepare(p)
        if lane:
            # backend aux must be shape-uniform across the lane's members
            backend.uniform_aux(group_plans)
            scan_group_meta.append((d, tuple(idxs)))
            scan_group_stacks.append(_stack_plans(group_plans))
        for i, p in zip(idxs, group_plans):
            plans[i] = p

    return TiledPlan(
        dataflow="mixed", tiles=tuple(tiles), merge_plan=merge_plan,
        plans=tuple(plans), shapes=tuple(shapes),
        block_shape=tuple(block_shape), backend=backend.name, budget=budget,
        fingerprint=fingerprint, interpret=interpret, scan_ok=False,
        occ_a_packed=_pack_bitmap(occ_a), occ_b_packed=_pack_bitmap(occ_b),
        scan_stacked=None, tile_dataflows=tuple(tile_dataflows),
        scan_group_meta=tuple(scan_group_meta),
        scan_group_stacks=tuple(scan_group_stacks))
