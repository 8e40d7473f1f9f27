"""Unified streaming work-list substrate for the Flexagon Pallas kernels.

All three dataflows enumerate the *same* effectual set
``{(i, k, j) : A[i,k] != 0 and B[k,j] != 0}`` — they differ only in the
order the pairs are visited and in the merge discipline applied to the
resulting psum blocks (paper §3.2, DESIGN.md §3/§18).  This module factors
that observation into one phase-1 artifact, :class:`StreamSchedule`: a
flat work list of (A slot, B slot) block pairs annotated with run
boundaries, consumed by exactly two Pallas kernels:

- :func:`stream_spmm` — the *block-run* kernel.  Work entries arrive
  destination-major (IP keeps its intersection order; OP is lexsorted by
  destination at plan time — the host sort plays the PSRAM set/tag
  lookup), so the MRN discipline degenerates to "accumulate while the
  run id is unchanged, flush when the fiber completes".  One fused
  ``pallas_call``: no HBM psum round trip between a streaming and a
  merging phase.
- :func:`stream_panel_spmm` — the *row-panel* kernel (Gustavson).  Work
  entries arrive row-major; the accumulator is a whole stationary output
  row panel in VMEM (GAMMA's fiber cache) and each psum merges at its
  follower's column offset immediately.

The block-run kernel walks the work list in chunks of up to
``MAX_CHUNK`` entries a grid step and stages its operands itself: B
blocks (and A blocks, when the A stack is too large to hold) stream from
HBM through a VMEM ring of DMA slots, the next chunk's copies in flight
while this chunk's dots occupy the MXU, and an A stack that fits
``A_RESIDENT_BYTES`` is copied into VMEM once per call.  A grouped call
whose stack does not fit holds its slots' A row panels instead, two at a
time, each copied in once while the slot before it runs.  The panel kernel
runs one grid step per entry with its operand streams described by
scalar-prefetched ``BlockSpec`` index maps, which Pallas double-buffers.

Every array in a :class:`StreamSchedule` is a pytree child, so schedules
**stack**: :func:`pad_schedule` pads the work and run axes to shared
maxima, and a stacked schedule drives the same kernels under ``lax.scan``
(tiled k-slab streaming) or ``shard_map`` (collective merge).  Padding
relies on jax's scatter semantics — out-of-bounds ``.at[].set`` rows are
dropped — so pad runs target a reserved out-of-bounds destination row.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..config import resolve_interpret
from ..core.dataflows import IPPlan, StreamPlan
from .common import compiler_params, grid_spec

__all__ = [
    "INDEX_MAPS",
    "SCHEDULE_KINDS",
    "StreamSchedule",
    "schedule_from_ip",
    "schedule_from_stream",
    "pad_schedule",
    "stream_spmm",
    "stream_panel_spmm",
    "row_schedules",
    "grouped_stream_spmm",
    "grouped_a_mode",
]

#: the two kernel disciplines a schedule can target: ``"dest"`` is the
#: destination-major block-run kernel (:func:`stream_spmm`, IP/OP),
#: ``"panel"`` the stationary row-panel kernel (:func:`stream_panel_spmm`,
#: Gustavson).  Static schedule aux — uniform across any stacked family.
SCHEDULE_KINDS = ("dest", "panel")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StreamSchedule:
    """Phase-1 work list + run boundaries for the streaming kernels.

    Pattern-only.  All arrays are pytree *children* (nothing
    shape-varying hides in the treedef), so schedules padded to common
    extents stack into slab/shard axes and trace through ``lax.scan``.
    """

    a_slot: np.ndarray     # (W,) int32 — A block slot per work entry
    b_slot: np.ndarray     # (W,) int32 — B block slot per work entry
    cj: np.ndarray         # (W,) int32 — destination block column (panel merge)
    is_first: np.ndarray   # (W,) int32 — run boundary flags
    is_last: np.ndarray
    run_id: np.ndarray     # (W,) int32 — output fiber index per entry
    run_ci: np.ndarray     # (R,) int32 — destination block coords per run
    run_cj: np.ndarray     # (R,) int32
    n_runs: int            # == R (static; uniform after pad_schedule)
    # -- self-description contract (DESIGN.md §19) ------------------------
    # The checker (repro.analysis.schedule) verifies schedules without
    # executing them; these fields let it split real work from padding.
    # ``kind`` is static aux (uniform across any stacked family — lanes
    # and shard stacks are same-dataflow); the three counters are (1,)
    # int32 pytree *children* because their values differ per stacked
    # member and treedefs must match for jnp.stack.
    kind: str = "dest"            # which kernel consumes it (SCHEDULE_KINDS)
    real_w: np.ndarray = None     # (1,) int32 — work entries that are real
    real_r: np.ndarray = None     # (1,) int32 — runs with real destinations
    oob: np.ndarray = None        # (1,) int32 — designated dropped pad row
                                  # (-1: schedule carries no padding)

    def __post_init__(self):
        if self.kind not in SCHEDULE_KINDS:
            raise ValueError(f"unknown schedule kind {self.kind!r}")
        # Host-side constructors may omit the contract fields: default to
        # "everything real, nothing padded".  Traced members rebuilt via
        # tree_unflatten always pass them, so no host op touches a tracer.
        if self.real_w is None:
            self.real_w = np.array([np.asarray(self.a_slot).size], np.int32)
        if self.real_r is None:
            self.real_r = np.array([self.n_runs], np.int32)
        if self.oob is None:
            self.oob = np.array([-1], np.int32)

    def tree_flatten(self):
        return ((self.a_slot, self.b_slot, self.cj, self.is_first,
                 self.is_last, self.run_id, self.run_ci, self.run_cj,
                 self.real_w, self.real_r, self.oob),
                (self.n_runs, self.kind))

    @classmethod
    def tree_unflatten(cls, aux, children):
        n_runs, kind = aux
        return cls(*children[:8], n_runs, kind, *children[8:])

    # -- concrete (host-side) accessors; not for traced members ----------
    @property
    def n_work(self) -> int:
        return int(np.asarray(self.a_slot).size)

    @property
    def n_real_work(self) -> int:
        return int(np.asarray(self.real_w).reshape(-1)[0])

    @property
    def n_real_runs(self) -> int:
        return int(np.asarray(self.real_r).reshape(-1)[0])

    @property
    def oob_row(self) -> int:
        return int(np.asarray(self.oob).reshape(-1)[0])

    @property
    def grid_steps(self) -> int:
        """Grid steps of the kernel that consumes this schedule: chunks of
        :func:`run_chunk` entries (block-run), one per entry (panel)."""
        return (run_grid_steps(self.n_work) if self.kind == "dest"
                else self.n_work)

    def describe(self) -> dict:
        """The self-description contract as one plain dict (checker/CLI)."""
        return {
            "kind": self.kind,
            "n_work": self.n_work,
            "n_runs": int(self.n_runs),
            "real_w": self.n_real_work,
            "real_r": self.n_real_runs,
            "oob_row": self.oob_row,
        }


def _empty_schedule(kind: str = "dest") -> StreamSchedule:
    z = np.zeros(0, np.int32)
    return StreamSchedule(z, z, z, z, z, z, z, z, 0, kind)


def _runs_from_boundaries(newrun: np.ndarray, w: int):
    is_first = np.ones(w, np.int32)
    is_first[1:] = newrun.astype(np.int32)
    is_last = np.ones(w, np.int32)
    is_last[:-1] = newrun.astype(np.int32)
    run_id = (np.cumsum(is_first) - 1).astype(np.int32)
    return is_first, is_last, run_id


def schedule_from_ip(plan: IPPlan) -> StreamSchedule:
    """IP: intersection lists are already destination-major (i, j, p)."""
    pair_a = np.asarray(plan.pair_a)
    pair_b = np.asarray(plan.pair_b)
    npairs = np.asarray(plan.npairs)
    mb, nb, p_max = pair_a.shape
    mask = np.arange(p_max)[None, None, :] < npairs[..., None]
    w = int(mask.sum())
    if w == 0:
        return _empty_schedule()
    a_slot = pair_a[mask].astype(np.int32)
    b_slot = pair_b[mask].astype(np.int32)
    ri, rj = np.nonzero(npairs)
    counts = npairs[ri, rj]
    cj = np.repeat(rj, counts).astype(np.int32)
    is_first = np.zeros(w, np.int32)
    is_first[np.cumsum(counts) - counts] = 1
    is_last = np.zeros(w, np.int32)
    is_last[np.cumsum(counts) - 1] = 1
    run_id = np.repeat(np.arange(ri.size), counts).astype(np.int32)
    # _pad_ip pads the pair axis but leaves npairs unchanged, so the mask
    # already excludes pad slots: everything here is real work.
    return StreamSchedule(a_slot, b_slot, cj, is_first, is_last, run_id,
                          ri.astype(np.int32), rj.astype(np.int32),
                          int(ri.size), "dest")


def schedule_from_stream(plan: StreamPlan, *, by_dest: bool) -> StreamSchedule:
    """OP/Gust: order a :class:`StreamPlan` work list into runs.

    ``by_dest=True`` (OP) lexsorts the k-major psum stream by destination
    block — the PSRAM set/tag lookup as a host sort — so the single fused
    kernel can merge in-VMEM with no HBM psum round trip.  ``by_dest=False``
    (Gust) keeps the i-major leader/follower order and forms one run per
    output row panel.

    Padded work entries (``_pad_stream``) carry an out-of-bounds ``ci``;
    they sort/group into their own runs whose destination row is dropped by
    the final scatter, so padded plans need no special handling here.
    """
    ci = np.asarray(plan.ci)
    cj = np.asarray(plan.cj)
    a_slot = np.asarray(plan.a_slot).astype(np.int32)
    b_slot = np.asarray(plan.b_slot).astype(np.int32)
    kind = "dest" if by_dest else "panel"
    w = int(ci.size)
    if w == 0:
        return _empty_schedule(kind)
    # seg_ptr[-1] counts the plan's real entries; _pad_stream pads carry
    # ci == oob_row > every real ci, so after the destination lexsort (and
    # trivially in the appended-at-tail panel order) the real entries are
    # exactly the first ``real`` positions.
    real = int(np.asarray(plan.seg_ptr)[-1])
    if by_dest:
        order = np.lexsort((cj, ci))
        ci, cj = ci[order], cj[order]
        a_slot, b_slot = a_slot[order], b_slot[order]
        newrun = (ci[1:] != ci[:-1]) | (cj[1:] != cj[:-1])
    else:
        newrun = ci[1:] != ci[:-1]
    is_first, is_last, run_id = _runs_from_boundaries(newrun, w)
    run_ci = ci[is_first == 1].astype(np.int32)
    run_cj = (cj[is_first == 1] if by_dest
              else np.zeros(run_ci.size)).astype(np.int32)
    real_r = int(run_id[real - 1]) + 1 if real > 0 else 0
    oob = int(ci[real]) if real < w else -1
    return StreamSchedule(a_slot, b_slot, cj.astype(np.int32),
                          is_first, is_last, run_id,
                          run_ci, run_cj, int(run_ci.size), kind,
                          np.array([real], np.int32),
                          np.array([real_r], np.int32),
                          np.array([oob], np.int32))


def pad_schedule(s: StreamSchedule, w_total: int, r_total: int,
                 oob_row: int) -> StreamSchedule:
    """Pad a schedule to shared (work, run) extents so schedules stack.

    Pad work entries are each a self-contained single-entry run (reset,
    one add of real-but-irrelevant blocks, flush) targeting the reserved
    run slot ``r_total - 1``; every pad run slot's destination row is
    ``oob_row`` (one past the output grid), so jax's scatter drops it.
    """
    w = int(np.asarray(s.a_slot).size)
    wpad = w_total - w
    rpad = r_total - s.n_runs
    if wpad < 0 or rpad < 0 or (wpad > 0 and rpad == 0):
        raise ValueError(
            f"cannot pad schedule (W={w}, R={s.n_runs}) to "
            f"(W={w_total}, R={r_total})")
    if wpad == 0 and rpad == 0:
        return s
    if s.oob_row >= 0 and s.oob_row != oob_row:
        # in-schedule pads (_pad_stream) and run-slot pads would target
        # different rows — the checker could no longer prove either dropped
        raise ValueError(
            f"conflicting pad destinations: schedule already pads to row "
            f"{s.oob_row}, pad_schedule asked for {oob_row}")
    zero = np.zeros(wpad, np.int32)
    one = np.ones(wpad, np.int32)
    return StreamSchedule(
        np.concatenate([np.asarray(s.a_slot, np.int32), zero]),
        np.concatenate([np.asarray(s.b_slot, np.int32), zero]),
        np.concatenate([np.asarray(s.cj, np.int32), zero]),
        np.concatenate([np.asarray(s.is_first, np.int32), one]),
        np.concatenate([np.asarray(s.is_last, np.int32), one]),
        np.concatenate([np.asarray(s.run_id, np.int32),
                        np.full(wpad, r_total - 1, np.int32)]),
        np.concatenate([np.asarray(s.run_ci, np.int32),
                        np.full(rpad, oob_row, np.int32)]),
        np.concatenate([np.asarray(s.run_cj, np.int32),
                        np.zeros(rpad, np.int32)]),
        r_total,
        s.kind,
        np.asarray(s.real_w, np.int32),
        np.asarray(s.real_r, np.int32),
        np.array([oob_row], np.int32),
    )


# -- index maps -------------------------------------------------------------
# Named module-level functions (not inline lambdas) so repro.analysis.jaxpr
# can trace and audit them by schedule kind without rebuilding a
# pallas_call.  Each takes a position plus the kernel's scalar-prefetch
# operands.  The block-run kernel copies whole blocks itself, so its maps
# take a work entry and give one block slot (of the A stack, the B stack
# and the ``runs`` output); the panel kernel's are ``BlockSpec`` index maps
# over its grid step and give one coordinate per block axis.


def _dest_a_map(e, sa, sb, fst, lst, rid):
    return sa[e]


def _dest_b_map(e, sa, sb, fst, lst, rid):
    return sb[e]


def _dest_out_map(e, sa, sb, fst, lst, rid):
    return rid[e]


def _panel_a_map(w, sa, sb, cj, fst, lst, rid):
    return (sa[w], 0, 0)


def _panel_b_map(w, sa, sb, cj, fst, lst, rid):
    return (sb[w], 0, 0)


def _panel_out_map(w, sa, sb, cj, fst, lst, rid):
    return (rid[w], 0, 0)


#: per schedule kind: (num_scalar_prefetch, coordinates per index,
#: {operand: index map}).  The checker's jaxpr pass audits exactly these
#: functions; keep them in sync with the kernels below.
INDEX_MAPS = {
    "dest": (5, 1, {"a": _dest_a_map, "b": _dest_b_map,
                    "out": _dest_out_map}),
    "panel": (6, 3, {"a": _panel_a_map, "b": _panel_b_map,
                     "out": _panel_out_map}),
}

#: bytes of VMEM the block-run kernel may give to holding the whole A block
#: stack for the call (a quarter of a v5e core's 128 MiB); a larger stack
#: streams its blocks through the DMA ring with B's.
A_RESIDENT_BYTES = 32 << 20
#: most work entries one grid step of the block-run kernel walks
MAX_CHUNK = 32
#: VMEM the block-run kernel asks for beyond its buffers (Mosaic's own
#: scratch: the dot's result, the cast at an emit)
VMEM_HEADROOM = 8 << 20


def run_chunk(w_total: int) -> int:
    """Work entries one grid step of the block-run kernel walks."""
    return max(1, min(MAX_CHUNK, int(w_total)))


def run_grid_steps(w_total: int) -> int:
    """Grid steps of the block-run kernel over ``w_total`` work entries."""
    return -(-int(w_total) // run_chunk(w_total))


def a_resident(a_nbytes: int) -> bool:
    """Whether the block-run kernel holds an A stack of ``a_nbytes`` in
    VMEM for the whole call instead of streaming its blocks."""
    return int(a_nbytes) <= A_RESIDENT_BYTES


def grouped_a_mode(n_slots: int, ka: int, block_nbytes: int) -> str:
    """How the grouped block-run kernel holds an A side of ``n_slots``
    slots of ``ka`` blocks of ``block_nbytes`` each: ``"resident"``, the
    whole stack in VMEM for the call, when it fits ``A_RESIDENT_BYTES``;
    else ``"panel"``, the live slots' row panels two at a time, when two
    fit; else ``"stream"``, each entry's A block through the DMA ring."""
    if a_resident(n_slots * ka * block_nbytes):
        return "resident"
    return "panel" if a_resident(2 * ka * block_nbytes) else "stream"


def _run_kernel(a_slot_ref, b_slot_ref, is_first_ref, is_last_ref,
                run_id_ref, *refs, chunk: int, w_total: int, resident: bool,
                live: bool = False, panel: Tuple[int, int] | None = None):
    """One grid step walks ``chunk`` consecutive work entries.

    Operands stay in HBM and the kernel copies them itself: each entry's
    B block (and A block, unless the whole A stack is held in ``a_buf``)
    into a ring of ``2 * chunk`` VMEM slots, the next chunk's copies
    started before this chunk's dots.  The chunk's dots run back to back,
    unrolled with no control flow between them, into ``psum_ref``; the
    copies and the fold of the entries into runs, in schedule order, are
    loops.  Runs accumulate in one of two f32
    slots, alternating per run, and leave by async copy to their ``runs``
    row; a slot's copy is waited on before the slot is reset and before
    the last step ends.

    With ``live`` a sixth scalar operand holds the count of live entries,
    known only on the device: entries from it on are neither fetched,
    multiplied nor folded, and a step with no live entry runs no dot.  The
    live entries must end on a run boundary.

    With ``panel = (ka, w)`` the entries come in slots of ``w`` that read
    only their slot's ``ka`` A blocks, which lie together in the A stack,
    and ``a_slot`` indexes a two-panel buffer ``a_buf`` (slot ``s``'s
    blocks in half ``s & 1``).  A slot's panel is copied in once, by one
    DMA started in the step after the one that holds the previous slot's
    first entry and waited on in the step that holds its own; a grid step
    (``chunk <= w``) spans at most two slots.  Slots past the live count
    fetch no panel.
    """
    prefetch = (a_slot_ref, b_slot_ref, is_first_ref, is_last_ref,
                run_id_ref)
    if live:
        n_live_ref, *refs = refs
    (a_hbm, b_hbm, runs_hbm, a_buf, b_buf, psum_ref, acc_ref, a_sem, b_sem,
     o_sem, started_ref, *stage) = refs
    stage_ref = stage[0] if stage else acc_ref
    c = pl.program_id(0)
    last_step = pl.num_programs(0) - 1
    tail = w_total % chunk          # entries in a short last chunk, or 0
    streamed = not resident and panel is None

    def each_entry(step, body):
        # body(j, e) for the step's entries in order; entries past the
        # static W exist only in a short last chunk, and past the live
        # count only when it is given (a step with none skips the loop)
        def visit(j, carry):
            e = step * chunk + j
            if live:
                pl.when(e < n_live_ref[0])(functools.partial(body, j, e))
            elif tail:
                pl.when(e < w_total)(functools.partial(body, j, e))
            else:
                body(j, e)
            return carry

        def walk():
            jax.lax.fori_loop(0, chunk, visit, 0)

        if live:
            pl.when(step * chunk < n_live_ref[0])(walk)
        else:
            walk()

    def operand_copies(e, slot):
        copies = [pltpu.make_async_copy(
            b_hbm.at[_dest_b_map(e, *prefetch)], b_buf.at[slot],
            b_sem.at[slot])]
        if streamed:
            copies.append(pltpu.make_async_copy(
                a_hbm.at[_dest_a_map(e, *prefetch)], a_buf.at[slot],
                a_sem.at[slot]))
        return copies

    def fetch(step):
        base = (step & 1) * chunk

        def start(j, e):
            for copy in operand_copies(e, base + j):
                copy.start()
        each_entry(step, start)

    def out_copy(s, run):
        return pltpu.make_async_copy(stage_ref.at[s], runs_hbm.at[run],
                                     o_sem.at[s])

    if panel:
        ka, w = panel
        n_entries = n_live_ref[0] if live else jnp.int32(w_total)

        def slot_panel(p):
            return pltpu.make_async_copy(a_hbm.at[pl.ds(p * ka, ka)],
                                         a_buf.at[pl.ds((p & 1) * ka, ka)],
                                         a_sem.at[p & 1])

        def first_slot(step):
            # the slot whose first entry may lie in ``step``, and whether
            # it does and is live
            p = jax.lax.div(step * chunk + w - 1, w)
            return p, p * w < jnp.minimum((step + 1) * chunk, n_entries)

    @pl.when(c == 0)
    def _():
        started_ref[0] = 0
        if resident:
            whole_a = pltpu.make_async_copy(a_hbm, a_buf, a_sem.at[0])
            whole_a.start()
        if panel:
            pl.when(n_entries > 0)(lambda: slot_panel(0).start())
        fetch(c)
        if resident:
            whole_a.wait()

    @pl.when(c < last_step)
    def _():
        fetch(c + 1)

    if panel:
        # the next slot's panel, into the half the slot before last held
        q, q_first = first_slot(jnp.maximum(c - 1, 0))

        @pl.when((c > 0) & q_first & ((q + 1) * w < n_entries))
        def _():
            slot_panel(q + 1).start()

    base = (c & 1) * chunk

    def arrive(j, e):
        for copy in operand_copies(e, base + j):
            copy.wait()
    each_entry(c, arrive)
    if panel:
        p, p_first = first_slot(c)
        pl.when(p_first)(lambda: slot_panel(p).wait())

    def dots():
        for j in range(chunk):
            # an entry past W (or the live count) multiplies a stale slot;
            # its product is not used
            e = jnp.minimum(c * chunk + j, w_total - 1)
            a = (a_buf[base + j] if streamed
                 else a_buf[_dest_a_map(e, *prefetch)])
            psum_ref[j] = jnp.dot(a, b_buf[base + j],
                                  preferred_element_type=jnp.float32)

    if live:
        pl.when(c * chunk < n_live_ref[0])(dots)
    else:
        dots()

    def fold(j, e):
        # MRN node discipline at block granularity: coordinate changed ->
        # new fiber; match -> add; fiber complete -> emit downstream.
        @pl.when(is_first_ref[e] == 1)
        def _():
            k = started_ref[0]

            @pl.when(k >= 2)
            def _():
                out_copy(k & 1, 0).wait()   # run k-2's emit left the slot
            acc_ref[k & 1] = jnp.zeros(acc_ref.shape[1:], acc_ref.dtype)
            started_ref[0] = k + 1

        s = (started_ref[0] - 1) & 1
        acc_ref[s] += psum_ref[j]

        @pl.when(is_last_ref[e] == 1)
        def _():
            if stage:
                stage_ref[s] = acc_ref[s].astype(stage_ref.dtype)
            out_copy(s, _dest_out_map(e, *prefetch)).start()

    each_entry(c, fold)

    @pl.when(c == last_step)
    def _():
        k = started_ref[0]
        for back in (1, 2):          # the last two runs' emits
            @pl.when(k >= back)
            def _():
                out_copy((k - back) & 1, 0).wait()


def stream_spmm(a_data: jax.Array, b_data: jax.Array, sched: StreamSchedule,
                *, out_grid: Tuple[int, int], out_shape: Tuple[int, int],
                out_dtype=jnp.float32,
                interpret: bool | None = None) -> jax.Array:
    """Run a destination-major schedule through the fused block-run kernel.

    ``a_data``/``b_data`` are the compressed operands' block stacks
    (``(nnzb, bm, bk)`` / ``(nnzb, bk, bn)``); they and the schedule's
    children may be traced (stacked slab/shard members under ``lax.scan``
    or ``shard_map``) — only array *shapes* shape the grid.

    The body is jit-cached per (shapes, config) signature: eager callers
    (an unjitted ``plan.apply`` serving loop) pay tracing once, then every
    apply runs the compiled executable — in interpret mode this is the
    difference between re-walking the grid in Python per call and one
    compiled scan over it.
    """
    return _stream_spmm(a_data, b_data, sched,
                        out_grid=tuple(out_grid),
                        out_shape=tuple(out_shape), out_dtype=out_dtype,
                        interpret=bool(resolve_interpret(interpret)))


def _block_runs(a_data, b_data, sched, *, chunk, resident, out_dtype,
                interpret, n_live=None, panel=None):
    """The block-run kernel's ``(n_runs, bm, bn)`` run sums, ``chunk``
    entries a grid step, the A stack held in VMEM when ``resident``.

    ``n_live`` (a traced int32 scalar, or None for every entry) stops the
    walk after that many entries; runs it does not reach are not written.
    ``panel = (ka, w)``: entry ``e`` belongs to slot ``e // w`` and reads
    only A blocks ``slot * ka`` to ``slot * ka + ka - 1``; the kernel
    holds the slots' row panels in VMEM two at a time (``chunk <= w``).
    """
    w_total = int(sched.a_slot.shape[0])
    bm, bk = a_data.shape[1], a_data.shape[2]
    bn = b_data.shape[2]
    ring = 2 * chunk
    a_slot = jnp.asarray(sched.a_slot, jnp.int32)
    if panel:
        ka, w = panel
        if chunk > w:
            raise ValueError(f"a grid step of {chunk} entries spans more "
                             f"than two slots of {w}")
        # each block's place in its slot's half of the two-panel buffer
        slot = jnp.arange(w_total, dtype=jnp.int32) // w
        a_slot = a_slot - (slot - (slot & 1)) * ka
        a_shape = (2 * ka, bm, bk)
    else:
        a_shape = a_data.shape if resident else (ring, bm, bk)
    a_buf = pltpu.VMEM(a_shape, a_data.dtype)
    b_buf = pltpu.VMEM((ring, bk, bn), b_data.dtype)
    psum = pltpu.VMEM((chunk, bm, bn), jnp.float32)
    acc = pltpu.VMEM((2, bm, bn), jnp.float32)
    vmem = [a_buf, b_buf, psum, acc]
    if jnp.dtype(out_dtype) != jnp.float32:  # lint: host-ok (static dtype)
        # runs leave through a staging slot of the output dtype
        vmem.append(pltpu.VMEM((2, bm, bn), out_dtype))
    vmem_bytes = sum(math.prod(v.shape) * jnp.dtype(v.dtype).itemsize
                     for v in vmem)
    live = () if n_live is None else (
        jnp.reshape(jnp.asarray(n_live, jnp.int32), (1,)),)
    spec = grid_spec(
        num_scalar_prefetch=5 + len(live),
        grid=(-(-w_total // chunk),),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=vmem[:4] + [
            pltpu.SemaphoreType.DMA((2 if panel else 1 if resident
                                     else ring,)),
            pltpu.SemaphoreType.DMA((ring,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ] + vmem[4:],
    )
    return pl.pallas_call(
        functools.partial(_run_kernel, chunk=chunk, w_total=w_total,
                          resident=resident, live=bool(live), panel=panel),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((sched.n_runs, bm, bn), out_dtype),
        compiler_params=compiler_params(
            ("arbitrary",), vmem_limit_bytes=vmem_bytes + VMEM_HEADROOM),
        interpret=interpret,
    )(a_slot,
      jnp.asarray(sched.b_slot, jnp.int32),
      jnp.asarray(sched.is_first, jnp.int32),
      jnp.asarray(sched.is_last, jnp.int32),
      jnp.asarray(sched.run_id, jnp.int32),
      *live, a_data, b_data)


@functools.partial(jax.jit, static_argnames=("out_grid", "out_shape",
                                             "out_dtype", "interpret"))
def _stream_spmm(a_data, b_data, sched, *, out_grid, out_shape, out_dtype,
                 interpret):
    w_total = int(sched.a_slot.shape[0])
    mb, nb = out_grid
    bm, bn = a_data.shape[1], b_data.shape[2]
    if w_total == 0:
        return jnp.zeros(out_shape, out_dtype)
    a_nbytes = a_data.size * jnp.dtype(a_data.dtype).itemsize
    runs = _block_runs(a_data, b_data, sched, chunk=run_chunk(w_total),
                       resident=a_resident(a_nbytes), out_dtype=out_dtype,
                       interpret=interpret)

    # Finished fibers stream to DRAM: place runs in the dense C image.
    # Pad runs carry an out-of-bounds row — the scatter drops them.
    c = jnp.zeros((mb, nb, bm, bn), out_dtype)
    c = c.at[jnp.asarray(sched.run_ci, jnp.int32),
             jnp.asarray(sched.run_cj, jnp.int32)].set(runs)
    c = c.swapaxes(1, 2).reshape(mb * bm, nb * bn)
    return c[: out_shape[0], : out_shape[1]]


# -- grouped block rows: many members, rows routed on the device -----------


def _row_schedule(mask: np.ndarray) -> StreamSchedule:
    """The ``ip_m`` schedule of one dense block row against a weight of
    block pattern ``mask`` (Kb, Nb), with one run for every output column,
    in column order: a column's kept blocks in k order, or for a column
    with none one entry against the zero block (B slot -1).  A slots are
    block columns of the row; B slots count the kept blocks in
    column-major (BCSC) order."""
    mask = np.asarray(mask, bool)
    cols = mask.copy()
    cols[0, ~mask.any(0)] = True
    js, ks = np.nonzero(cols.T)
    kept = mask[ks, js]
    b_slot = np.where(kept, np.cumsum(kept) - 1, -1).astype(np.int32)
    is_first, is_last, run_id = _runs_from_boundaries(js[1:] != js[:-1],
                                                      js.size)
    nb = mask.shape[1]
    return StreamSchedule(ks.astype(np.int32), b_slot, js.astype(np.int32),
                          is_first, is_last, run_id, np.zeros(nb, np.int32),
                          np.arange(nb, dtype=np.int32), nb, "dest")


def row_schedules(masks: np.ndarray) -> StreamSchedule:
    """Phase 1 for a stack of members at once: each member's
    :func:`_row_schedule`, padded to shared extents and stacked on a
    leading member axis.  Run ``j`` of every member is output column
    ``j``; a member padded to the longest work list gets pad entries whose
    one run, after the columns' runs, is dropped.

    ``masks``: (E, Kb, Nb) block patterns, one per member.
    """
    scheds = [_row_schedule(m) for m in np.asarray(masks, bool)]
    w_max = max(s.n_work for s in scheds)
    ragged = any(s.n_work < w_max for s in scheds)
    r_total = scheds[0].n_runs + int(ragged)
    padded = [pad_schedule(s, w_max, r_total, oob_row=1) for s in scheds]
    return jax.tree.map(lambda *xs: np.stack(xs), *padded)


def grouped_stream_spmm(a_data: jax.Array, b_data: jax.Array,
                        members: StreamSchedule, slot_member: jax.Array,
                        n_live: jax.Array, *, out_cols: int,
                        out_dtype=jnp.float32,
                        interpret: bool | None = None) -> jax.Array:
    """Row panels ``A_s @ B_{member(s)}`` for the live slots, through the
    block-run kernel, with the routing known only on the device.

    ``a_data`` holds ``S`` slots of ``Ka`` blocks each, slot-major: slot
    ``s`` is one dense block row.  ``b_data`` holds ``E`` members of ``Wb``
    packed blocks each, member-major, in the order of ``members``
    (:func:`row_schedules`), then one zero block.  ``slot_member`` (S,)
    names each slot's member and ``n_live`` how many leading slots are
    live.  The work list is every slot's copy of its member's schedule,
    built here on the device; the kernel walks the ``n_live`` live slots'
    entries and fetches nothing past them.  It holds A as
    :func:`grouped_a_mode` says from the shapes: the whole stack, or each
    live slot's row panel fetched once, or each entry's block.  Returns
    the output's blocks, ``(S, out_cols / bn, bm, bn)``, slot-major as
    ``a_data`` is: the kernel's runs as they are.  Dead slots' blocks are not written.  One
    compiled program serves every routing.
    """
    return _grouped_spmm(a_data, b_data, members, slot_member, n_live,
                         out_cols=int(out_cols), out_dtype=out_dtype,
                         interpret=bool(resolve_interpret(interpret)))


@functools.partial(jax.jit, static_argnames=("out_cols", "out_dtype",
                                             "interpret"))
def _grouped_spmm(a_data, b_data, members, slot_member, n_live, *, out_cols,
                  out_dtype, interpret):
    n_slots = int(slot_member.shape[0])
    w = members.a_slot.shape[1]
    ka = a_data.shape[0] // n_slots
    bm, bn = a_data.shape[1], b_data.shape[2]
    sched = _grouped_schedule(members, slot_member, n_live, ka=ka,
                             n_b=b_data.shape[0])
    mode = grouped_a_mode(n_slots, ka,
                          bm * a_data.shape[2] * a_data.dtype.itemsize)
    panel = (ka, w) if mode == "panel" else None
    runs = _block_runs(a_data, b_data, sched,
                       chunk=run_chunk(w if panel else n_slots * w),
                       resident=mode == "resident", out_dtype=out_dtype,
                       interpret=interpret,
                       n_live=jnp.asarray(n_live, jnp.int32) * w, panel=panel)
    return runs.reshape(n_slots, members.n_runs, bm, bn)[:, :out_cols // bn]


def _grouped_schedule(members: StreamSchedule, slot_member, n_live, *,
                     ka: int, n_b: int) -> StreamSchedule:
    """The work list of :func:`grouped_stream_spmm`: every slot's copy of
    its member's schedule, slot-major, with slot ``s``'s A blocks at
    ``s * ka`` and the members' ``n_b`` packed B blocks (the zero block
    last); its real entries and runs are the ``n_live`` leading slots'."""
    n_slots = int(slot_member.shape[0])
    n_members, w = members.a_slot.shape
    r = members.n_runs
    wb = (n_b - 1) // n_members
    m = jnp.asarray(slot_member, jnp.int32)
    s = jnp.arange(n_slots, dtype=jnp.int32)[:, None]
    n_live = jnp.asarray(n_live, jnp.int32)

    def per_slot(x):
        return jnp.asarray(x, jnp.int32)[m]

    b_slot = per_slot(members.b_slot)
    flat = lambda x: x.reshape(-1)  # noqa: E731
    return StreamSchedule(
        flat(s * ka + per_slot(members.a_slot)),
        flat(jnp.where(b_slot < 0, n_members * wb, m[:, None] * wb + b_slot)),
        flat(per_slot(members.cj)),
        flat(per_slot(members.is_first)), flat(per_slot(members.is_last)),
        flat(s * r + per_slot(members.run_id)),
        jnp.zeros(n_slots * r, jnp.int32), jnp.zeros(n_slots * r, jnp.int32),
        n_slots * r, "dest", jnp.reshape(n_live * w, (1,)),
        jnp.reshape(n_live * r, (1,)), jnp.full((1,), -1, jnp.int32))


def _panel_kernel(a_slot_ref, b_slot_ref, cj_ref, is_first_ref, is_last_ref,
                  run_id_ref, a_ref, b_ref, o_ref, acc_ref, *, bn: int):
    del a_slot_ref, b_slot_ref, run_id_ref
    w = pl.program_id(0)

    @pl.when(is_first_ref[w] == 1)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    psum = jnp.dot(a_ref[0], b_ref[0], preferred_element_type=jnp.float32)
    # merge into the stationary output fiber at the follower's coordinate
    acc_ref[:, pl.ds(cj_ref[w] * bn, bn)] += psum

    @pl.when(is_last_ref[w] == 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def stream_panel_spmm(a_data: jax.Array, b_data: jax.Array,
                      sched: StreamSchedule, *, out_grid: Tuple[int, int],
                      out_shape: Tuple[int, int], out_dtype=jnp.float32,
                      interpret: bool | None = None) -> jax.Array:
    """Run a row-major schedule through the stationary row-panel kernel.

    One ``(bm, Nb*bn)`` fp32 accumulator panel lives in VMEM per run
    (Gustavson: GAMMA's fiber cache); psums merge immediately at their
    follower's column offset, so C is written once per row panel.

    Jit-cached like :func:`stream_spmm` — eager serving loops trace once
    per signature and then run the compiled executable.
    """
    return _stream_panel_spmm(a_data, b_data, sched,
                              out_grid=tuple(out_grid),
                              out_shape=tuple(out_shape),
                              out_dtype=out_dtype,
                              interpret=bool(resolve_interpret(interpret)))


@functools.partial(jax.jit, static_argnames=("out_grid", "out_shape",
                                             "out_dtype", "interpret"))
def _stream_panel_spmm(a_data, b_data, sched, *, out_grid, out_shape,
                       out_dtype, interpret):
    w_total = int(sched.a_slot.shape[0])
    mb, nb = out_grid
    bm, bk = a_data.shape[1], a_data.shape[2]
    bn = b_data.shape[2]
    if w_total == 0:
        return jnp.zeros(out_shape, out_dtype)
    n_padded = nb * bn

    spec = grid_spec(
        num_scalar_prefetch=6,
        grid=(w_total,),
        in_specs=[
            pl.BlockSpec((1, bm, bk), _panel_a_map),
            pl.BlockSpec((1, bk, bn), _panel_b_map),
        ],
        out_specs=pl.BlockSpec((1, bm, n_padded), _panel_out_map),
        scratch_shapes=[pltpu.VMEM((bm, n_padded), jnp.float32)],
    )
    runs = pl.pallas_call(
        functools.partial(_panel_kernel, bn=bn),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((sched.n_runs, bm, n_padded),
                                       out_dtype),
        compiler_params=compiler_params(("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(sched.a_slot, jnp.int32),
      jnp.asarray(sched.b_slot, jnp.int32),
      jnp.asarray(sched.cj, jnp.int32),
      jnp.asarray(sched.is_first, jnp.int32),
      jnp.asarray(sched.is_last, jnp.int32),
      jnp.asarray(sched.run_id, jnp.int32),
      a_data, b_data)

    c = jnp.zeros((mb, bm, n_padded), out_dtype)
    c = c.at[jnp.asarray(sched.run_ci, jnp.int32)].set(runs)
    c = c.reshape(mb * bm, n_padded)
    return c[: out_shape[0], : out_shape[1]]
