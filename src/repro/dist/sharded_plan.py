"""``ShardedPlan`` — per-shard plans composed into one ``shard_map`` apply.

The distribution layer (DESIGN.md §13): when phase 1 is handed a ``mesh``,
the dataflow's :class:`repro.dist.partition.Partitioner` splits the block
grid into one uniform sub-problem per shard, each shard gets an ordinary
:class:`repro.api.FlexagonPlan` (or a :class:`repro.memory.TiledPlan` when
its slice still exceeds the memory budget — tiling stays orthogonal to
placement), and ``ShardedPlan.apply`` runs them all:

- on a **collective-merge capable** backend (``ExecutionBackend
  .collective_merge``: ``execute`` accepts traced plan leaves), the
  per-shard plans are padded to one uniform pytree shape, stacked leaf-wise,
  and executed inside a single ``jax.shard_map`` — each device
  slices out its own plan, runs the unchanged ``ExecutionBackend.execute``,
  and OP k-slab partitions merge their partial sums with one
  ``jax.lax.psum`` (the MRN's merge phase lifted to the interconnect — the
  top tier of the merge hierarchy);
- otherwise (a backend without ``collective_merge`` — both ``reference``
  and ``pallas`` declare it; the pallas kernels consume shape-uniform
  ``StreamSchedule`` work lists, so stacked shard members trace cleanly)
  the shards unroll into a sequential loop on one device with the same
  combine — numerically identical, still jit-compatible.
  :attr:`ShardedPlan.runs_sharded` says which path an apply takes.

The containment hierarchy stays clean: ``ShardedPlan → TiledPlan →
FlexagonPlan``, every level exposing the same ``apply`` surface.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..backends import get_backend
from ..backends.base import TABLE3_FORMATS
from ..core import dataflows as df
from ..core.selector import DataflowEstimate, LayerShape, TPUSpec, estimate
from ..memory.budget import MemoryBudget, output_bytes
from ..memory.tiled_plan import (_build_sub_plan, _pack_bitmap, _pad_ip,
                                 _pad_layout, _pad_stream, _stack_plans,
                                 _unpack_bitmap, plan_tiled)
from ..memory.tiling import Tile
from .partition import (DistPartition, Partitioner, merge_ici_bytes,
                        mesh_device_count, resolve_shards)

__all__ = ["ShardedPlan", "plan_sharded"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedPlan:
    """Phase-1 output for one SpMSpM partitioned across a device mesh.

    Mirrors the :class:`repro.api.FlexagonPlan` / :class:`repro.memory
    .TiledPlan` surface (``apply`` / ``__call__`` / ``matches`` /
    ``with_backend`` / ``pack_a`` / ``pack_b`` …) so every caller of the
    plan API can hold any of the three.  ``tiles`` are the per-shard
    sub-grids (uniform half-open block ranges along the partition axis);
    ``ici_bytes`` is the priced cross-shard merge traffic (nonzero only for
    k-slab partitions, whose partial sums all-reduce across the mesh).
    """

    dataflow: str
    axis: str                                # "m" | "k" | "n"
    n_shards: int
    mesh: Any                                # jax Mesh (hashable) or None
    partition: DistPartition
    tiles: Tuple[Tile, ...]                  # per-shard sub-grids
    plans: Tuple[Any, ...]                   # FlexagonPlan | TiledPlan each
    shapes: Tuple[int, int, int]
    block_shape: Tuple[int, int, int]
    padded_grid: Tuple[int, int, int]
    backend: str
    budget: Optional[MemoryBudget]
    fingerprint: str
    interpret: Optional[bool]
    shard_ok: bool                           # plans uniform → shard_map path
    ici_bytes: float
    occ_a_packed: Tuple[bytes, Tuple[int, int]]
    occ_b_packed: Tuple[bytes, Tuple[int, int]]
    #: per-shard plans stacked leaf-wise for the shard_map path (phase 1)
    shard_stacked: Any = None

    # -- pytree plumbing -------------------------------------------------
    def tree_flatten(self):
        aux = (self.dataflow, self.axis, self.n_shards, self.mesh,
               self.partition, self.tiles, self.shapes, self.block_shape,
               self.padded_grid, self.backend, self.budget, self.fingerprint,
               self.interpret, self.shard_ok, self.ici_bytes,
               self.occ_a_packed, self.occ_b_packed)
        return (tuple(self.plans), self.shard_stacked), aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        plans, shard_stacked = children
        (dataflow, axis, n_shards, mesh, partition, tiles, shapes,
         block_shape, padded_grid, backend, budget, fingerprint, interpret,
         shard_ok, ici_bytes, occ_a, occ_b) = aux
        return cls(dataflow, axis, n_shards, mesh, partition, tiles,
                   tuple(plans), shapes, block_shape, padded_grid, backend,
                   budget, fingerprint, interpret, shard_ok, ici_bytes,
                   occ_a, occ_b, shard_stacked)

    # -- phase-1 byproducts ----------------------------------------------
    @property
    def is_mixed(self) -> bool:
        """Heterogeneous per-tile dataflows inside the shards (§14)."""
        return self.dataflow == "mixed"

    @property
    def out_major(self) -> str:
        if self.is_mixed:
            return "csr"       # dense-assembled disjoint regions (cf. §14)
        return df.OUTPUT_MAJOR[self.dataflow]

    @property
    def formats(self):
        from ..core.formats import SparseFormat

        if self.is_mixed:
            return (SparseFormat.BCSR, SparseFormat.BCSR)
        return TABLE3_FORMATS[self.dataflow]

    @property
    def use_pallas(self) -> bool:
        return self.backend == "pallas"

    @property
    def collective(self) -> str:
        """The cross-shard merge collective ("psum" for k-slab partitions)."""
        return "psum" if self.axis == "k" and self.n_shards > 1 else "none"

    @property
    def occ_a(self) -> np.ndarray:
        return _unpack_bitmap(self.occ_a_packed)

    @property
    def occ_b(self) -> np.ndarray:
        return _unpack_bitmap(self.occ_b_packed)

    @property
    def mesh_shape(self) -> Optional[Tuple[int, ...]]:
        if self.mesh is None:
            return None
        return tuple(np.asarray(self.mesh.devices).shape)

    @property
    def runs_sharded(self) -> bool:
        """Does :meth:`apply` take the ``shard_map`` path across the mesh
        (``False``: every shard runs in sequence on one device)?"""
        return (self.shard_ok and self.n_shards > 1
                and getattr(get_backend(self.backend), "collective_merge",
                            False)
                and mesh_device_count(self.mesh) >= self.n_shards)

    @property
    def dist_stats(self) -> dict:
        """Shard/collective telemetry (surfaced by ``ServeEngine.stats``)."""
        return {"mesh_shape": self.mesh_shape, "shards": self.n_shards,
                "axis": self.axis, "collective": self.collective,
                "ici_bytes": float(self.ici_bytes)}

    @property
    def estimate(self) -> DataflowEstimate:
        """Aggregate over shards (shards run in parallel, so ``compute_s`` /
        ``memory_s`` take the slowest shard; bytes sum)."""
        ests = [p.estimate for p in self.plans]
        return DataflowEstimate(
            dataflow=self.dataflow,
            flops=sum(e.flops for e in ests),
            bytes_a=sum(e.bytes_a for e in ests),
            bytes_b=sum(e.bytes_b for e in ests),
            bytes_c=sum(e.bytes_c for e in ests),
            bytes_psum=sum(e.bytes_psum for e in ests) + self.ici_bytes,
            compute_s=max(e.compute_s for e in ests),
            memory_s=max(e.memory_s for e in ests),
        )

    def matches(self, a, b) -> bool:
        """Do these operands carry the planned (whole-operation) pattern?"""
        from ..api import _fingerprint, _pattern_of

        (m, k), occ_a = _pattern_of(a, self.block_shape[:2])
        (_, n), occ_b = _pattern_of(b, self.block_shape[1:])
        return _fingerprint(occ_a, occ_b, (m, k, n),
                            self.block_shape) == self.fingerprint

    def with_backend(self, backend) -> "ShardedPlan":
        """Re-target onto another backend (re-partitions from the stored
        bitmaps so each substrate gets the plan shapes it expects).  Mixed
        plans re-target shard by shard instead — each shard's per-tile
        dataflow choices are pinned, never re-selected."""
        be = get_backend(backend)
        if self.is_mixed:
            plans = tuple(p.with_backend(be) for p in self.plans)
            return dataclasses.replace(self, backend=be.name, plans=plans,
                                       shard_ok=False, shard_stacked=None)
        return plan_sharded(
            dataflow=self.dataflow, occ_a=self.occ_a, occ_b=self.occ_b,
            shapes=self.shapes, block_shape=self.block_shape, mesh=self.mesh,
            partition=DistPartition(axis=self.axis, shards=self.n_shards),
            budget=self.budget, backend=be, interpret=self.interpret,
            fingerprint=self.fingerprint)

    # -- packing (host-side conveniences, phase-1 style) ------------------
    def _pack(self, x, fmt, block_shape):
        from ..api import SparseOperand

        if isinstance(x, SparseOperand):
            x = np.asarray(x.todense())
        return SparseOperand.from_dense(np.asarray(x), format=fmt,
                                        block_shape=block_shape)

    def pack_a(self, a):
        """Whole-operand compression in the planned A format (shards ingest
        dense slices, so packing is a storage convenience here)."""
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
        """Execute C = A @ B across the shards.  jit-compatible, zero host
        work; collective-capable backends run one ``shard_map``.  The
        device work is named ``dist.sharded.apply`` in a trace."""
        with jax.named_scope("dist.sharded.apply"):
            return self._apply_inner(a, b, out_dtype)

    def _apply_inner(self, a, b, out_dtype=jnp.float32) -> jax.Array:
        m, k, n = self.shapes
        bm, bk, bn = self.block_shape
        mp, kp, np_ = self.padded_grid
        a_d = self._densify(a).astype(jnp.float32)
        b_d = self._densify(b).astype(jnp.float32)
        a_d = jnp.pad(a_d, ((0, mp * bm - a_d.shape[0]),
                            (0, kp * bk - a_d.shape[1])))
        b_d = jnp.pad(b_d, ((0, kp * bk - b_d.shape[0]),
                            (0, np_ * bn - b_d.shape[1])))
        if self.runs_sharded:
            out = self._apply_shard_map(a_d, b_d)
        else:
            out = self._apply_serial(a_d, b_d)
        return out[:m, :n].astype(out_dtype)

    __call__ = apply

    def _apply_serial(self, a_d: jax.Array, b_d: jax.Array) -> jax.Array:
        """Unrolled fallback: same shard sub-plans, sequential execution,
        explicit combine (sum for k-slabs, concatenation for disjoint
        output partitions)."""
        bm, bk, bn = self.block_shape
        parts = []
        for tile, plan in zip(self.tiles, self.plans):
            a_s = a_d[tile.i0 * bm: tile.i1 * bm,
                      tile.k0 * bk: tile.k1 * bk]
            b_s = b_d[tile.k0 * bk: tile.k1 * bk,
                      tile.j0 * bn: tile.j1 * bn]
            parts.append(plan.apply(a_s, b_s, jnp.float32))
        if self.axis == "k":
            out = parts[0]
            for p in parts[1:]:
                out = out + p
            return out
        return jnp.concatenate(parts, axis=0 if self.axis == "m" else 1)

    def _flat_mesh(self):
        """The mesh's devices as a 1-D ("shards",) mesh (first n_shards)."""
        # device objects are host metadata, never traced
        devs = np.asarray(self.mesh.devices).reshape(-1)[: self.n_shards]  # lint: host-ok
        return jax.sharding.Mesh(devs, ("shards",))

    def _apply_shard_map(self, a_d: jax.Array, b_d: jax.Array) -> jax.Array:
        """One ``shard_map`` over the flattened mesh: plan leaves ride in
        sharded-stacked form, each device slices out its own sub-plan and
        runs the backend's unchanged ``execute``; k-slab partitions merge
        partial sums with ``psum`` (the top tier of the merge hierarchy)."""
        P = jax.sharding.PartitionSpec
        a_spec, b_spec, out_spec = {
            "m": (P("shards", None), P(None, None), P("shards", None)),
            "k": (P(None, "shards"), P("shards", None), P(None, None)),
            "n": (P(None, None), P(None, "shards"), P(None, "shards")),
        }[self.axis]
        stacked = self.shard_stacked
        if stacked is None:            # e.g. plan rebuilt by hand
            stacked = _stack_plans(list(self.plans))
        axis = self.axis

        def body(plan_stk, a_blk, b_blk):
            sub = jax.tree_util.tree_map(lambda leaf: leaf[0], plan_stk)
            out = sub.apply(a_blk, b_blk, jnp.float32)
            if axis == "k":
                out = jax.lax.psum(out, "shards")
            return out

        fn = jax.shard_map(body, mesh=self._flat_mesh(),
                           in_specs=(P("shards"), a_spec, b_spec),
                           out_specs=out_spec, check_vma=False)
        return fn(stacked, a_d, b_d)


def plan_sharded(*, dataflow: str, occ_a: np.ndarray, occ_b: np.ndarray,
                 shapes: Tuple[int, int, int],
                 block_shape: Tuple[int, int, int], mesh,
                 partition: Optional[DistPartition],
                 budget: Optional[MemoryBudget], backend,
                 interpret: Optional[bool], fingerprint: str,
                 spec: TPUSpec = TPUSpec(), policy=None
                 ) -> Optional[ShardedPlan]:
    """Phase 1 for the multi-device case.

    Returns ``None`` when the (mesh, partition) pair resolves to a single
    shard — the caller then builds an ordinary single-device plan.
    ``dataflow="mixed"`` shards row bands of the output grid and lets each
    shard hold its own per-tile dataflow mix (``policy`` prices the tiles);
    mixed shards always take the serial-fallback apply.
    """
    part = Partitioner.for_dataflow(dataflow, partition)
    n_shards = resolve_shards(mesh, partition)
    if n_shards <= 1:
        return None

    from ..api import FlexagonPlan

    mixed = dataflow == "mixed"
    if mixed and budget is None:
        raise ValueError(
            "dataflow='mixed' requires a memory_budget (DESIGN.md §14)")
    m, k, n = shapes
    bm, bk, bn = block_shape
    shard_slices = part.shard_bitmaps(occ_a, occ_b, n_shards)
    padded = part.padded_grid((occ_a.shape[0], occ_a.shape[1],
                               occ_b.shape[1]), n_shards)

    # one shared estimate + fingerprint keeps per-shard treedefs identical,
    # which is what lets the plans stack into one shard_map (cf. the OP
    # k-slab scan in repro.memory.tiled_plan); mixed shards never stack, so
    # they keep per-shard estimates instead
    t0 = shard_slices[0][0]
    shared_est = None if mixed else estimate(
        LayerShape(m=(t0.i1 - t0.i0) * bm, k=(t0.k1 - t0.k0) * bk,
                   n=(t0.j1 - t0.j0) * bn,
                   density_a=float(occ_a.mean()) if occ_a.size else 0.0,
                   density_b=float(occ_b.mean()) if occ_b.size else 0.0,
                   block=tuple(block_shape)), dataflow, spec)

    plans: List[Any] = []
    tiled_any = False
    for idx, (tile, occ_at, occ_bt) in enumerate(shard_slices):
        shape_a = ((tile.i1 - tile.i0) * bm, (tile.k1 - tile.k0) * bk)
        shape_b = ((tile.k1 - tile.k0) * bk, (tile.j1 - tile.j0) * bn)
        sub = None
        if budget is not None:
            # tiling within the shard: placement stays orthogonal to tiling
            sub = plan_tiled(dataflow=dataflow, occ_a=occ_at, occ_b=occ_bt,
                             shapes=(shape_a[0], shape_a[1], shape_b[1]),
                             block_shape=tuple(block_shape), budget=budget,
                             backend=backend, interpret=interpret,
                             fingerprint=f"{fingerprint}/shard{idx}",
                             spec=spec, policy=policy)
        if sub is not None:
            tiled_any = True
        else:
            d = dataflow
            if mixed:
                # this shard's slice fits in one resident tile: its "mix"
                # is the policy's single choice for the slice
                from ..memory.tiled_plan import mixed_tile_dataflows

                d = mixed_tile_dataflows(
                    occ_at, occ_bt, tuple(block_shape), budget,
                    backend=backend, policy=policy, spec=spec,
                    fingerprint=f"{fingerprint}/shard{idx}",
                    tiles=[Tile(0, occ_at.shape[0], 0, occ_at.shape[1],
                                0, occ_bt.shape[1])])[0]
            sub = _build_sub_plan(
                d, occ_at, occ_bt, tuple(block_shape), backend,
                f"{fingerprint}/shard", interpret, spec, est=shared_est)
        plans.append(sub)

    shard_ok = False
    if not mixed and not tiled_any \
            and getattr(backend, "collective_merge", False):
        nnz_a = max(p.a_layout.nnzb for p in plans)
        nnz_b = max(p.b_layout.nnzb for p in plans)
        for p in plans:
            p.a_layout = _pad_layout(p.a_layout, nnz_a)
            p.b_layout = _pad_layout(p.b_layout, nnz_b)
        if isinstance(plans[0].index_plan, df.IPPlan):
            p_max = max(int(p.index_plan.pair_a.shape[2]) for p in plans)
            for p in plans:
                p.index_plan = _pad_ip(p.index_plan, p_max)
            shard_ok = True
        else:
            w_max = max(int(p.index_plan.a_slot.shape[0]) for p in plans)
            # transposed (N-stationary) executors scatter on the dual grid
            t0 = shard_slices[0][0]
            oob = (t0.j1 - t0.j0) if dataflow.endswith("_n") \
                else (t0.i1 - t0.i0)
            for p in plans:
                p.index_plan = _pad_stream(p.index_plan, w_max, oob)
            shard_ok = w_max > 0

    for p in plans:
        if isinstance(p, FlexagonPlan) and p.aux is None:
            p.aux = backend.prepare(p)
    if shard_ok:
        # backend aux schedules must stack too (shape-uniform per shard)
        backend.uniform_aux(plans)

    dt = budget.dtype_bytes if budget is not None else 4
    c_bytes = output_bytes(occ_a, occ_b, (bm, bn), dt)
    ici = merge_ici_bytes(part.axis, n_shards, c_bytes)
    obs.get_registry().gauge("dist.ici_bytes").set(float(ici))

    return ShardedPlan(
        dataflow=dataflow, axis=part.axis, n_shards=n_shards, mesh=mesh,
        partition=partition if partition is not None else DistPartition(),
        tiles=tuple(t for t, _, _ in shard_slices), plans=tuple(plans),
        shapes=tuple(shapes), block_shape=tuple(block_shape),
        padded_grid=tuple(padded), backend=backend.name, budget=budget,
        fingerprint=fingerprint, interpret=interpret, shard_ok=shard_ok,
        ici_bytes=float(ici), occ_a_packed=_pack_bitmap(occ_a),
        occ_b_packed=_pack_bitmap(occ_b),
        shard_stacked=_stack_plans(plans) if shard_ok else None)
