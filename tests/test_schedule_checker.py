"""Schedule-checker tests (DESIGN.md §19).

Covers the five invariant families of ``repro.analysis.schedule`` —
coverage, exclusivity/race-freedom, bounds, padding soundness,
determinism — three ways:

- **property tests** (random sparsity patterns × all six dataflows ×
  mixed × 1/2/8 shards): the checker accepts every planner-emitted
  schedule with zero diagnostics;
- **mutation tests**: each family rejects a schedule mutated to violate
  exactly that invariant, surfacing *its* stable diagnostic code;
- **cache regression**: ``verify_cache`` catches a re-targeted plan
  re-admitted into the LRU with a stale/foreign schedule (fails against
  the PR-9 verifier, which never looked at ``plan.aux``).
"""
import dataclasses
import functools

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import DistPartition, MemoryBudget, PlanCache, flexagon_plan
from repro.analysis import errors_of, verify_cache, verify_plan
from repro.core import dataflows as df
from repro.core import random_sparse_dense

BS = (16, 16, 16)


def _operands(seed=0, shape=(64, 48, 80), da=0.35, db=0.45):
    rng = np.random.default_rng(seed)
    m, k, n = shape
    a = random_sparse_dense(rng, (m, k), density=da, block_shape=BS[:2])
    b = random_sparse_dense(rng, (k, n), density=db, block_shape=BS[1:])
    return a, b


def _codes(diags):
    return {d.code for d in diags}


@functools.lru_cache(maxsize=None)
def _base_plan(dataflow="op_m"):
    """One cached pallas plan for the mutation tests (never mutated in
    place — every mutation goes through ``dataclasses.replace`` copies)."""
    # dense enough that destination runs merge several (A, B) pairs —
    # the determinism mutation needs a multi-entry run to reorder
    a, b = _operands(seed=3, da=0.8, db=0.8)
    return flexagon_plan(a, b, dataflow=dataflow, block_shape=BS,
                         backend="pallas", verify=False)


def _with_schedule(plan, sched):
    return dataclasses.replace(plan, aux={**plan.aux,
                                          "stream_schedule": sched})


def _mutate(sched, **arrays):
    """Replace schedule fields with modified *copies* of the originals."""
    return dataclasses.replace(
        sched, **{k: np.asarray(v) for k, v in arrays.items()})


def test_verify_cache_catches_retargeted_readmission():
    """Pre-fix regression (the PR-9 verifier returned ``[]`` here).

    A serving loop re-targets a cached plan with ``with_backend`` and
    re-admits it into the LRU.  If the re-admitted plan carries a stale
    or foreign aux schedule (here: another pattern's schedule — exactly
    what a buggy re-admission that skips ``prepare`` produces), only the
    original insertion was ever verified: ``verify_cache`` checked key
    agreement and plan *structure* but never the aux schedule, so the
    corrupt entry was served silently.
    """
    cache = PlanCache()
    a, b = _operands(seed=0)
    plan = cache.get(a, b, dataflow="op_m", block_shape=BS,
                     backend="pallas", verify=False)
    assert "stream_schedule" in plan.aux
    key = next(iter(cache._plans))

    a2, b2 = _operands(seed=9, shape=(48, 64, 48), da=0.2, db=0.3)
    other = flexagon_plan(a2, b2, dataflow="op_m", block_shape=BS,
                          backend="pallas", verify=False)
    stale = dataclasses.replace(plan, aux=dict(plan.aux))
    stale.aux["stream_schedule"] = other.aux["stream_schedule"]
    cache._plans[key] = stale          # the LRU re-admission

    codes = _codes(verify_cache(cache))
    assert codes & {"schedule-coverage", "schedule-determinism",
                    "schedule-bounds"}, codes


# ---------------------------------------------------------------------------
# property tests: the checker accepts every planner-emitted schedule
# ---------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000),
       m=st.sampled_from((32, 48, 64)),
       k=st.sampled_from((32, 48, 64)),
       n=st.sampled_from((32, 48, 64)),
       da=st.floats(0.1, 0.6),
       db=st.floats(0.1, 0.6))
def test_checker_accepts_all_dataflows(seed, m, k, n, da, db):
    """Random sparsity x {six dataflows, mixed}: zero diagnostics."""
    a, b = _operands(seed=seed, shape=(m, k, n), da=da, db=db)
    budget = MemoryBudget(l1_bytes=1024, l2_bytes=2048)
    for dataflow in list(df.DATAFLOWS) + ["mixed"]:
        plan = flexagon_plan(
            a, b, dataflow=dataflow, block_shape=BS, backend="pallas",
            verify=False,
            memory_budget=budget if dataflow == "mixed" else None)
        diags = verify_plan(plan)
        assert not errors_of(diags), (dataflow, [str(d) for d in diags])


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10_000), da=st.floats(0.15, 0.5),
       db=st.floats(0.15, 0.5))
def test_checker_accepts_sharded_stacks(seed, da, db):
    """Random sparsity x {1, 2, 8 shards}: zero errors, stacks uniform."""
    a, b = _operands(seed=seed, shape=(128, 48, 64), da=da, db=db)
    for shards in (1, 2, 8):
        plan = flexagon_plan(
            a, b, dataflow="op_m", block_shape=BS, backend="pallas",
            verify=False,
            partition=DistPartition(shards=shards) if shards > 1 else None)
        diags = verify_plan(plan)
        assert not errors_of(diags), (shards, [str(d) for d in diags])


# ---------------------------------------------------------------------------
# mutation tests: each invariant family rejects its violated schedule
# ---------------------------------------------------------------------------


def test_mutation_structure_boundary_flags():
    """A cleared run-opening is_first breaks the accumulator discipline."""
    plan = _base_plan()
    s = plan.aux["stream_schedule"]
    assert s.n_real_work > 0
    flags = np.asarray(s.is_first).copy()
    flags[0] = 0
    codes = _codes(verify_plan(_with_schedule(plan, _mutate(s,
                                                            is_first=flags))))
    assert "schedule-structure" in codes, codes


def test_mutation_bounds_operand_slot():
    """An out-of-range gather slot would DMA past the stored block stack."""
    plan = _base_plan()
    s = plan.aux["stream_schedule"]
    a_stored = plan.a_layout.rows.shape[0]
    slots = np.asarray(s.a_slot).copy()
    slots[0] = a_stored + 5
    codes = _codes(verify_plan(_with_schedule(plan, _mutate(s,
                                                            a_slot=slots))))
    assert "schedule-bounds" in codes, codes


def test_mutation_bounds_run_destination():
    """A real run scattering outside the output grid is out of bounds."""
    plan = _base_plan()
    s = plan.aux["stream_schedule"]
    m, _, _ = plan.shapes
    rows_g = -(-m // BS[0])
    ci = np.asarray(s.run_ci).copy()
    ci[0] = rows_g + 3
    codes = _codes(verify_plan(_with_schedule(plan, _mutate(s, run_ci=ci))))
    assert "schedule-bounds" in codes, codes


def test_mutation_race_duplicate_destination():
    """Two real runs claiming one C block: last writer wins, data lost."""
    plan = _base_plan()
    s = plan.aux["stream_schedule"]
    assert s.n_real_runs >= 2
    ci = np.asarray(s.run_ci).copy()
    cj = np.asarray(s.run_cj).copy()
    ci[1], cj[1] = ci[0], cj[0]
    codes = _codes(verify_plan(_with_schedule(plan, _mutate(s, run_ci=ci,
                                                            run_cj=cj))))
    assert "schedule-race" in codes, codes


def test_mutation_pad_run_inside_grid():
    """A pad run retargeted inside the grid would overwrite real output.

    Also proves the positive direction first: a canonically *padded*
    schedule (what uniform_aux emits for stacked families) passes the
    whole checker, including the determinism re-derivation.
    """
    from repro.kernels.stream import pad_schedule

    plan = _base_plan()
    s = plan.aux["stream_schedule"]
    m, _, _ = plan.shapes
    rows_g = -(-m // BS[0])
    oob = s.oob_row if s.oob_row >= 0 else rows_g
    padded = pad_schedule(s, s.n_work + 3, int(s.n_runs) + 1, oob)
    assert not errors_of(verify_plan(_with_schedule(plan, padded)))

    ci = np.asarray(padded.run_ci).copy()
    ci[-1] = 0                       # pad run now aliases a real output row
    codes = _codes(verify_plan(_with_schedule(plan, _mutate(padded,
                                                            run_ci=ci))))
    assert "schedule-pad" in codes, codes


def test_mutation_coverage_retargeted_pair():
    """Rewriting one gathered slot drops a pair and invents another."""
    plan = _base_plan()
    s = plan.aux["stream_schedule"]
    a_stored = plan.a_layout.rows.shape[0]
    assert a_stored >= 2
    slots = np.asarray(s.a_slot).copy()
    slots[0] = (slots[0] + 1) % a_stored
    codes = _codes(verify_plan(_with_schedule(plan, _mutate(s,
                                                            a_slot=slots))))
    assert "schedule-coverage" in codes, codes


def test_mutation_determinism_reordered_merge():
    """A multiset-preserving reorder inside one run changes fp32
    accumulation order — everything else passes, determinism catches it."""
    plan = _base_plan()
    s = plan.aux["stream_schedule"]
    rid = np.asarray(s.run_id)
    a_slot = np.asarray(s.a_slot).copy()
    b_slot = np.asarray(s.b_slot).copy()
    idx = next((i for i in range(1, s.n_real_work)
                if rid[i] == rid[i - 1]
                and (a_slot[i] != a_slot[i - 1]
                     or b_slot[i] != b_slot[i - 1])), None)
    assert idx is not None, "expected a multi-entry run in the base plan"
    a_slot[idx - 1], a_slot[idx] = a_slot[idx], a_slot[idx - 1]
    b_slot[idx - 1], b_slot[idx] = b_slot[idx], b_slot[idx - 1]
    diags = verify_plan(_with_schedule(plan, _mutate(s, a_slot=a_slot,
                                                     b_slot=b_slot)))
    codes = _codes(diags)
    assert codes == {"schedule-determinism"}, [str(d) for d in diags]


def test_missing_schedule_on_pallas_plan():
    """A pallas plan whose aux lost its schedule is rejected outright."""
    plan = _base_plan()
    stripped = dataclasses.replace(
        plan, aux={k: v for k, v in plan.aux.items()
                   if k != "stream_schedule"})
    codes = _codes(verify_plan(stripped))
    assert "schedule-missing" in codes, codes


def test_stack_uniformity_on_sharded_plan():
    """A shard whose schedule extents drift breaks the shard_map stack."""
    from repro.kernels.stream import pad_schedule

    a, b = _operands(seed=5, shape=(128, 48, 64))
    plan = flexagon_plan(a, b, dataflow="op_m", block_shape=BS,
                         backend="pallas", verify=False,
                         partition=DistPartition(shards=2))
    assert plan.shard_ok and len(plan.plans) == 2
    member = plan.plans[1]
    s = member.aux["stream_schedule"]
    m_mem, _, _ = member.shapes
    rows_g = -(-m_mem // BS[0])
    oob = s.oob_row if s.oob_row >= 0 else rows_g
    grown = pad_schedule(s, s.n_work + 3, int(s.n_runs) + 1, oob)
    bad = dataclasses.replace(
        plan, plans=(plan.plans[0], _with_schedule(member, grown)))
    codes = _codes(verify_plan(bad))
    assert "schedule-stack" in codes, codes


# ---------------------------------------------------------------------------
# lint rule, index-map audit, unified CLI
# ---------------------------------------------------------------------------


def test_lint_schedule_call_rule(tmp_path):
    """Raw StreamSchedule/pallas_call outside kernels/ fails lint; the
    same construct inside kernels/ (and outside repro/) is allowed."""
    from repro.analysis import lint_paths

    pkg = tmp_path / "repro"
    (pkg / "kernels").mkdir(parents=True)
    bad = pkg / "helper.py"
    bad.write_text("from repro.kernels.stream import StreamSchedule\n"
                   "s = StreamSchedule(a, b, c, d, e, f, g, h, 4)\n")
    ok_kernel = pkg / "kernels" / "fused.py"
    ok_kernel.write_text("import jax.experimental.pallas as pl\n"
                         "out = pl.pallas_call(kernel, grid=(4,))\n")

    codes = {d.code for d in lint_paths([str(bad)])}
    assert "schedule-call" in codes, codes
    assert "schedule-call" not in {d.code
                                   for d in lint_paths([str(ok_kernel)])}


def test_index_map_report_clean_and_empty():
    from repro.analysis import index_map_report

    for kind in ("dest", "panel"):
        report = index_map_report(kind, 64, 16)
        assert report.clean, [str(d) for d in report.diagnostics]
        assert report.aval_hashes
    empty = index_map_report("dest", 0, 0)
    assert empty.clean and not empty.aval_hashes


def test_unified_cli_usage_and_lint():
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    env_src = str(root / "src")

    def run(*argv):
        import os
        env = dict(os.environ, PYTHONPATH=env_src)
        return subprocess.run([sys.executable, "-m", "repro.analysis",
                               *argv], cwd=root, env=env,
                              capture_output=True, text=True)

    usage = run()
    assert usage.returncode == 2
    assert "subcommands" in usage.stdout + usage.stderr

    lint = run("lint", "src/repro/analysis/schedule.py")
    assert lint.returncode == 0, lint.stdout + lint.stderr

    unknown = run("frobnicate")
    assert unknown.returncode == 2
