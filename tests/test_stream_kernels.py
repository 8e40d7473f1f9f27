"""Pallas fast path (DESIGN.md §18): StreamSchedule kernels end to end.

The contracts under test, all in interpret mode on CPU:

- **scan parity** — pallas declares ``scan_streaming``; a tiled plan's
  stacked sub-plan schedules (padded to shared extents by ``uniform_aux``)
  run through ``lax.scan`` with traced leaves and match the dense
  reference for every dataflow;
- **collective parity** — pallas declares ``collective_merge``; a
  ``ShardedPlan`` runs the kernels inside ``shard_map`` with a psum merge
  on a virtual mesh and matches the dense reference;
- **mixed fused lanes** — ``dataflow="mixed"`` groups same-shape tiles
  into lanes; a pallas lane scans as one fused call and stays correct;
- **dense escape** — plans whose work ratio reaches ``DENSE_THRESHOLD``
  take the plain-MXU matmul hatch (``"dense"`` aux marker) in every
  dataflow, plans just under it stay sparse, and numerics are unchanged
  either way;
- **block-run kernel** — the chunked grid with its operand DMA ring and
  resident A gives the runs of a per-entry accumulation bit for bit:
  runs shorter and longer than a chunk, ragged last chunks, stacked
  schedules under ``lax.scan``;
- **schedule padding** — ``pad_schedule``'s self-contained pad runs target
  a dropped out-of-bounds row and reject impossible extents;
- **alignment diagnostic** — compiled (interpret=False) plans with
  MXU-misaligned blocks surface a typed ``block-alignment`` verify_plan
  diagnostic instead of a Mosaic crash.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import MemoryBudget, ShardedPlan, TiledPlan, flexagon_plan
from repro.analysis import verify_plan
from repro.backends import get_backend
from repro.backends.pallas import DENSE_THRESHOLD
from repro.core import random_sparse_dense
from repro.core.dataflows import DATAFLOWS
from repro.kernels import StreamSchedule, pad_schedule, schedule_from_ip
from repro.launch.mesh import make_virtual_mesh

BS = (8, 8, 8)
#: small enough to force k-slab tiling on the 48-deep case below
SLABS = MemoryBudget(l1_bytes=2 << 10, l2_bytes=8 << 10)


def _case(seed=0, m=32, k=48, n=40, da=0.4, db=0.6):
    rng = np.random.default_rng(seed)
    a = random_sparse_dense(rng, (m, k), density=da, block_shape=BS[:2])
    b = random_sparse_dense(rng, (k, n), density=db, block_shape=BS[1:])
    return a, b


# ---------------------------------------------------------------------------
# Scan parity: stacked schedules through lax.scan, all six dataflows
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dataflow", DATAFLOWS)
def test_tiled_scan_parity(dataflow):
    a, b = _case(seed=1)
    plan = flexagon_plan(a, b, dataflow=dataflow, block_shape=BS,
                         backend="pallas", memory_budget=SLABS)
    assert isinstance(plan, TiledPlan) and plan.n_tiles >= 2
    # only OP tiles into uniform k-slabs; with pallas declaring
    # scan_streaming those now take the lax.scan path (IP/Gust row/col
    # bands unroll by construction, scan or not)
    assert plan.scan_ok == dataflow.startswith("op"), (
        f"{dataflow}: scan_ok should track the OP-slab structure")
    np.testing.assert_allclose(np.asarray(plan.apply(a, b)), a @ b,
                               rtol=1e-4, atol=1e-4)


def test_scan_stack_padded_to_shared_extents():
    """uniform_aux pads sibling schedules so stacked leaves are uniform."""
    a, b = _case(seed=1)
    plan = flexagon_plan(a, b, dataflow="op_m", block_shape=BS,
                         backend="pallas", memory_budget=SLABS)
    assert isinstance(plan, TiledPlan) and plan.scan_ok
    scheds = [p.aux["stream_schedule"] for p in plan.plans]
    assert len({s.a_slot.shape for s in scheds}) == 1
    assert len({s.n_runs for s in scheds}) == 1


# ---------------------------------------------------------------------------
# Collective parity: ShardedPlan through shard_map + psum, all six dataflows
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dataflow", DATAFLOWS)
def test_sharded_collective_parity(dataflow, virtual_mesh):
    a, b = _case(seed=3)
    plan = flexagon_plan(a, b, dataflow=dataflow, block_shape=BS,
                         backend="pallas", mesh=virtual_mesh)
    assert isinstance(plan, ShardedPlan)
    assert plan.shard_ok, (
        f"{dataflow}: pallas declares collective_merge, the shard stack "
        "should take the shard_map path")
    np.testing.assert_allclose(np.asarray(plan.apply(a, b)), a @ b,
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Mixed fused lanes
# ---------------------------------------------------------------------------


def test_mixed_fused_lane_parity():
    a, b = _case(seed=4, m=96, k=96, n=96, da=0.3, db=0.7)
    plan = flexagon_plan(a, b, dataflow="mixed", block_shape=BS,
                         backend="pallas",
                         memory_budget=MemoryBudget(l1_bytes=10000,
                                                    l2_bytes=40000))
    assert isinstance(plan, TiledPlan) and plan.n_tiles >= 2
    # same-shape same-dataflow tiles grouped into >= 1 fused scan lane
    assert plan.scan_group_meta, "expected at least one fused pallas lane"
    np.testing.assert_allclose(np.asarray(plan.apply(a, b)), a @ b,
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Dense escape hatch
# ---------------------------------------------------------------------------


def test_dense_escape_marker_and_parity():
    rng = np.random.default_rng(5)
    a = random_sparse_dense(rng, (32, 32), density=0.95, block_shape=BS[:2])
    b = random_sparse_dense(rng, (32, 32), density=0.95, block_shape=BS[1:])
    plan = flexagon_plan(a, b, dataflow="ip_m", block_shape=BS,
                         backend="pallas")
    assert "dense" in plan.aux, "near-dense pattern should take the hatch"
    np.testing.assert_allclose(np.asarray(plan.apply(a, b)), a @ b,
                               rtol=1e-4, atol=1e-4)


def _ratio_case(kept, seed=6):
    """Dense A (2 x 4 blocks) times a B with ``kept`` of its 4 x 4 blocks:
    the plan's work ratio is exactly ``kept / 16`` in every dataflow."""
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((16, 32)).astype(np.float32)
    b = np.zeros((32, 32), np.float32)
    for blk in rng.permutation(16)[:kept]:
        r, c = divmod(int(blk), 4)
        b[r * 8:(r + 1) * 8, c * 8:(c + 1) * 8] = \
            rng.standard_normal((8, 8)).astype(np.float32) + 0.1
    return a, b


@pytest.mark.parametrize("kept", [7, 8], ids=["under", "at"])
@pytest.mark.parametrize("dataflow", DATAFLOWS)
def test_dense_escape_boundary(dataflow, kept):
    a, b = _ratio_case(kept)
    plan = flexagon_plan(a, b, dataflow=dataflow, block_shape=BS,
                         backend="pallas")
    ratio = get_backend("pallas")._work_ratio(plan)
    assert ratio == kept / 16
    assert ("dense" in plan.aux) == (ratio >= DENSE_THRESHOLD)
    assert "stream_schedule" in plan.aux
    np.testing.assert_allclose(np.asarray(plan.apply(a, b)), a @ b,
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Block-run kernel: chunked grid, operand DMA ring, resident A
# ---------------------------------------------------------------------------


def _schedule_of_runs(lengths, n_a, n_b, rng):
    """A destination-major schedule whose runs have the given lengths."""
    w, r = sum(lengths), len(lengths)
    ends = np.cumsum(lengths)
    is_first = np.zeros(w, np.int32)
    is_first[ends - np.asarray(lengths)] = 1
    is_last = np.zeros(w, np.int32)
    is_last[ends - 1] = 1
    run_id = np.repeat(np.arange(r), lengths).astype(np.int32)
    slots = [rng.integers(0, n, w).astype(np.int32) for n in (n_a, n_b)]
    return StreamSchedule(*slots, np.zeros(w, np.int32), is_first, is_last,
                          run_id, np.arange(r, dtype=np.int32),
                          np.zeros(r, np.int32), r)


def _runs_in_entry_order(a, b, s, out_dtype):
    """Plain per-entry accumulation in schedule order: reset on is_first,
    add each pair's f32 dot, emit the run on is_last."""
    out = [None] * s.n_runs
    acc = None
    for e in range(s.n_work):
        if s.is_first[e]:
            acc = jnp.zeros((a.shape[1], b.shape[2]), jnp.float32)
        acc = acc + jnp.dot(a[s.a_slot[e]], b[s.b_slot[e]],
                            preferred_element_type=jnp.float32)
        if s.is_last[e]:
            out[s.run_id[e]] = np.asarray(acc.astype(out_dtype))
    return out


#: three members' (Ka=3, Nb=4) block masks for grouped calls: member 1
#: keeps fewer blocks (pad entries) and no block of output column 2 (an
#: entry against the zero block); 8 entries a slot
GROUPED_MASKS = np.array([[[1, 0, 1, 1], [1, 1, 0, 0], [0, 1, 1, 1]],
                          [[1, 1, 0, 0], [0, 1, 0, 1], [0, 0, 0, 1]],
                          [[1, 1, 1, 1], [1, 0, 1, 0], [0, 1, 0, 1]]], bool)


@dataclasses.dataclass(frozen=True)
class Grouped:
    """A grouped call: each slot's member, the live slots, entries a grid
    step (at most a slot's 8, so a step spans at most two slots)."""

    slot_member: tuple
    n_live: int
    chunk: int
    out_dtype: type = jnp.float32


def _grouped_operands(case, rng):
    """A slot-major A stack, the members' packed B blocks (the zero block
    last) and the grouped work list of ``case``."""
    from repro.kernels.stream import _grouped_schedule, row_schedules

    members = row_schedules(GROUPED_MASKS)
    n_members, ka = GROUPED_MASKS.shape[:2]
    wb = int(GROUPED_MASKS.sum((1, 2)).max())
    a = jnp.asarray(rng.standard_normal((len(case.slot_member) * ka, 8, 16)),
                    jnp.float32)
    b = jnp.asarray(rng.standard_normal((n_members * wb + 1, 16, 8)),
                    jnp.float32).at[-1].set(0.0)
    sched = _grouped_schedule(members, jnp.asarray(case.slot_member),
                              case.n_live, ka=ka, n_b=b.shape[0])
    return a, b, members, jax.tree.map(np.asarray, sched)


def _grouped_product(a, b, slot_member, n_live):
    """Each live slot's dense row panel times its member's dense weight,
    rebuilt from the masks and the packed blocks: (n_live, bm, Nb * bn)."""
    n_members, ka, nb = GROUPED_MASKS.shape
    wb = (b.shape[0] - 1) // n_members
    out = []
    for s, m in enumerate(slot_member[:n_live]):
        js, ks = np.nonzero(GROUPED_MASKS[m].T)     # the packed order
        dense = np.zeros((ka, nb) + b.shape[1:], np.float32)
        dense[ks, js] = np.asarray(b)[m * wb:m * wb + js.size]
        rows = np.asarray(a)[s * ka:(s + 1) * ka].transpose(1, 0, 2)
        out.append(rows.reshape(a.shape[1], -1) @
                   dense.transpose(0, 2, 1, 3).reshape(rows.size // a.shape[1],
                                                       -1))
    return np.asarray(out).reshape(n_live, a.shape[1], nb * b.shape[2])


#: name -> (run lengths of each stacked member, entries a grid step, out
#: dtype[, live entries]); one member runs unstacked, two run stacked under
#: lax.scan; with a live count the kernel stops after that many entries
#: (on a run boundary) and only the runs before it are compared.  A
#: ``Grouped`` case is a grouped call, run with its slots' A row panels
#: held two at a time as well as with A resident or streamed
BLOCK_RUN_CASES = {
    "runs-shorter-and-longer-than-chunk": ([[2, 7, 1, 12, 3]], 4,
                                           jnp.float32),
    "several-runs-end-in-one-chunk": ([[1, 1, 1, 2, 3, 1]], 8, jnp.float32),
    "w-below-chunk": ([[2, 1]], 16, jnp.float32),
    "w-not-a-multiple-of-chunk": ([[5, 6]], 4, jnp.float32),
    "one-entry-a-step": ([[3, 1, 2]], 1, jnp.float32),
    "bf16-output": ([[4, 1, 3]], 3, jnp.bfloat16),
    "stacked-under-scan": ([[3, 2, 4], [1, 5]], 4, jnp.float32),
    "live-count-stops-mid-step": ([[2, 7, 1, 12, 3]], 4, jnp.float32, 10),
    "live-count-at-a-step-edge": ([[4, 4, 3]], 4, jnp.float32, 8),
    "live-count-zero": ([[3, 1, 2]], 2, jnp.float32, 0),
    "live-count-whole-list": ([[5, 6]], 4, jnp.bfloat16, 11),
    "grouped-no-live-slot": Grouped((0, 1, 2, 0), 0, 3),
    "grouped-one-live-slot": Grouped((1, 0, 2, 0), 1, 3),
    "grouped-every-slot-live": Grouped((0, 1, 1, 2), 4, 3),
    "grouped-chunk-divides-slot": Grouped((2, 1, 0, 0, 1), 3, 4),
    "grouped-bf16-output": Grouped((1, 2, 0), 3, 5, jnp.bfloat16),
}


@pytest.mark.parametrize("resident", [True, False],
                         ids=["a-resident", "a-ring"])
@pytest.mark.parametrize("case", list(BLOCK_RUN_CASES))
def test_block_run_kernel_matches_per_entry_order(case, resident):
    """The chunked kernel gives, bit for bit, the runs of a per-entry
    accumulation in schedule order, with A held in VMEM or (as for a
    stack over ``A_RESIDENT_BYTES``) streamed through the DMA ring, and
    with a live-entry count read on the device."""
    from repro.kernels.stream import (A_RESIDENT_BYTES, _block_runs,
                                      a_resident)

    if isinstance(BLOCK_RUN_CASES[case], Grouped):
        _check_grouped_panels(BLOCK_RUN_CASES[case], resident)
        return
    members, chunk, out_dtype, *live = BLOCK_RUN_CASES[case]
    rng = np.random.default_rng(sum(map(len, members)) + chunk)
    a = jnp.asarray(rng.standard_normal((5, 8, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((7, 16, 8)), jnp.float32)
    scheds = [_schedule_of_runs(lengths, 5, 7, rng) for lengths in members]
    want = [_runs_in_entry_order(a, b, s, out_dtype) for s in scheds]

    def runs(sched):
        return _block_runs(a, b, sched, chunk=chunk, resident=resident,
                           out_dtype=out_dtype, interpret=True,
                           n_live=jnp.int32(live[0]) if live else None)

    if live:        # the runs that end before the live count
        ends = np.cumsum(members[0])
        want = [w[:int(np.sum(ends <= live[0]))] for w in want]

    if len(scheds) == 1:
        got = [np.asarray(runs(scheds[0]))]
    else:
        w_max = max(s.n_work for s in scheds)
        r_total = max(s.n_runs for s in scheds) + 1
        padded = [pad_schedule(s, w_max, r_total, oob_row=99)
                  for s in scheds]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *padded)
        got = np.asarray(jax.lax.scan(lambda c, s: (c, runs(s)), 0,
                                      stacked)[1])
    for g, w in zip(got, want):
        assert g.dtype == np.dtype(out_dtype)
        for r, run in enumerate(w):
            np.testing.assert_array_equal(g[r], run, err_msg=f"run {r}")
    assert a_resident(A_RESIDENT_BYTES)
    assert not a_resident(A_RESIDENT_BYTES + 1)


def _check_grouped_panels(case, resident):
    """A grouped call with each live slot's A row panel held in VMEM gives
    the runs of the same call with A resident or streamed, and of a
    per-entry accumulation, bit for bit, and the plain product of each
    slot's rows and its member's weight.  The panel run goes through the
    TPU interpreter, which simulates the kernel's DMAs and semaphores and
    finds any read or write that races one of them."""
    from jax._src.pallas.mosaic.interpret import \
        interpret_pallas_call as tpu_interpret
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.stream import _block_runs

    rng = np.random.default_rng(len(case.slot_member) + case.n_live)
    a, b, members, sched = _grouped_operands(case, rng)
    w, (ka, nb) = members.a_slot.shape[1], GROUPED_MASKS.shape[1:]
    n_live, r = case.n_live, members.n_runs

    def runs(interpret, **mode):
        got = _block_runs(a, b, sched, chunk=case.chunk,
                          out_dtype=case.out_dtype, interpret=interpret,
                          n_live=jnp.int32(n_live * w), **mode)
        # the live slots' output columns; the pad run is not an output
        return np.asarray(got).reshape(-1, r, *got.shape[1:])[:n_live, :nb]

    panel = runs(pltpu.InterpretParams(detect_races=True), resident=False,
                 panel=(ka, w))
    assert not tpu_interpret.races.races_found
    other = runs(True, resident=resident)
    np.testing.assert_array_equal(panel, other)
    want = _runs_in_entry_order(a, b, sched, case.out_dtype)
    for s in range(n_live):
        for j in range(nb):
            np.testing.assert_array_equal(panel[s, j], want[s * r + j],
                                          err_msg=f"slot {s} column {j}")
    assert panel.dtype == np.dtype(case.out_dtype)
    plain = _grouped_product(a, b, case.slot_member, n_live)
    got = panel.astype(np.float32).transpose(0, 2, 1, 3).reshape(plain.shape)
    tol = 1e-5 if case.out_dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(got, plain, rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# Schedule padding
# ---------------------------------------------------------------------------


def test_pad_schedule_contract():
    a, b = _case(seed=7, m=16, k=16, n=16)
    plan = flexagon_plan(a, b, dataflow="ip_m", block_shape=BS,
                         backend="pallas")
    s = plan.aux["stream_schedule"]
    w, r = int(s.a_slot.size), s.n_runs
    padded = pad_schedule(s, w + 3, r + 2, oob_row=99)
    assert padded.a_slot.size == w + 3 and padded.n_runs == r + 2
    # pad entries are self-contained single-entry runs on the reserved slot
    assert (padded.is_first[w:] == 1).all()
    assert (padded.is_last[w:] == 1).all()
    assert (padded.run_id[w:] == r + 1).all()
    assert (padded.run_ci[r:] == 99).all()
    # no-op pad returns the schedule unchanged
    assert pad_schedule(s, w, r, oob_row=99) is s
    # shrinking, and padding work without a reserved pad run, both reject
    with pytest.raises(ValueError):
        pad_schedule(s, w - 1, r, oob_row=99)
    with pytest.raises(ValueError):
        pad_schedule(s, w + 1, r, oob_row=99)


# ---------------------------------------------------------------------------
# MXU alignment diagnostic
# ---------------------------------------------------------------------------


def test_block_alignment_diagnostic_compiled_only():
    a, b = _case(seed=9, m=16, k=16, n=16)
    # interpret mode: any block size is fine
    plan = flexagon_plan(a, b, dataflow="ip_m", block_shape=BS,
                         backend="pallas", interpret=True)
    codes = {d.code for d in verify_plan(plan)}
    assert "block-alignment" not in codes
    # compiled: (8, 8, 8) violates the (8, 128) fp32 lane rule -> typed
    # diagnostic at plan time (verify=True would raise, so build unverified)
    plan = flexagon_plan(a, b, dataflow="ip_m", block_shape=BS,
                         backend="pallas", interpret=False, verify=False)
    diags = verify_plan(plan)
    codes = {d.code for d in diags}
    assert "block-alignment" in codes
    msg = next(d for d in diags if d.code == "block-alignment").message
    assert "bk=8 % 128" in msg and "bn=8 % 128" in msg
