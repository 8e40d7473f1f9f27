"""Plan-once / execute-many operator API (repro.api).

The contract under test (DESIGN.md §2): ``flexagon_plan`` does ALL host-side
work — occupancy, selector, compression layouts, index plans — exactly once;
``plan.apply`` is pure jnp, jit-compatible, and never re-plans.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
import repro.api as api
from repro import (FlexagonPipeline, FlexagonPlan, PlanCache, SparseFormat,
                   SparseOperand, flexagon_plan)
from repro.core import dataflows as df
from repro.core.formats import random_sparse_dense
from repro.kernels import spmm_ref

BS = (8, 8, 8)


def _case(seed=0, m=24, k=40, n=32, da=0.4, db=0.6):
    rng = np.random.default_rng(seed)
    a = random_sparse_dense(rng, (m, k), density=da, block_shape=(8, 8))
    b = random_sparse_dense(rng, (k, n), density=db, block_shape=(8, 8))
    return a, b


# ---------------------------------------------------------------------------
# SparseOperand
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["bcsr", "bcsc", "csr", "csc"])
def test_operand_dense_roundtrip(fmt):
    a, _ = _case()
    op = SparseOperand.from_dense(a, format=fmt, block_shape=(8, 8))
    np.testing.assert_allclose(np.asarray(op.todense()), a, rtol=1e-6)
    assert op.fmt is SparseFormat.of(fmt)


def test_operand_convert_between_all_formats():
    a, _ = _case()
    op = SparseOperand.from_dense(a, format="bcsr", block_shape=(8, 8))
    for fmt in ("bcsc", "csr", "csc", "bcsr"):
        conv = op.convert(fmt, block_shape=(8, 8))
        np.testing.assert_allclose(np.asarray(conv.todense()), a, rtol=1e-6)
    # scalar formats count scalars, block formats count blocks
    assert op.convert("csr").nnz == int((a != 0).sum())


def test_operand_pytree_roundtrip():
    a, _ = _case()
    op = SparseOperand.from_dense(a, format="bcsc", block_shape=(8, 8))
    leaves, treedef = jax.tree_util.tree_flatten(op)
    assert all(hasattr(l, "shape") for l in leaves)
    op2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert op2.fmt is op.fmt and op2.shape == op.shape
    np.testing.assert_array_equal(np.asarray(op2.todense()),
                                  np.asarray(op.todense()))
    # operands traverse jit boundaries like any pytree
    dense = jax.jit(lambda o: o.todense())(op)
    np.testing.assert_allclose(np.asarray(dense), a, rtol=1e-6)


# ---------------------------------------------------------------------------
# FlexagonPlan: correctness through the new entry point
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dataflow", df.DATAFLOWS)
def test_all_dataflows_match_ref(dataflow):
    a, b = _case(seed=3)
    ref = np.asarray(spmm_ref(a, b))
    plan = flexagon_plan(a, b, dataflow=dataflow, block_shape=BS)
    out = np.asarray(plan.apply(a, b))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
    assert plan.out_major == df.OUTPUT_MAJOR[dataflow]


@pytest.mark.parametrize("dataflow", df.DATAFLOWS)
def test_all_dataflows_match_ref_pallas(dataflow):
    a, b = _case(seed=4, m=16, k=24, n=16)
    ref = np.asarray(spmm_ref(a, b))
    plan = flexagon_plan(a, b, dataflow=dataflow, block_shape=BS,
                         use_pallas=True)
    out = np.asarray(plan.apply(a, b))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_auto_selection_and_estimate():
    a, b = _case(seed=5)
    plan = flexagon_plan(a, b, block_shape=BS)
    assert plan.dataflow in df.DATAFLOWS
    assert plan.estimate.dataflow == plan.dataflow
    assert plan.estimate.time_s > 0
    assert plan.formats == api._TABLE3_FORMATS[plan.dataflow]


# ---------------------------------------------------------------------------
# The phase-1-exactly-once contract
# ---------------------------------------------------------------------------


def test_apply_does_no_plan_building(monkeypatch):
    """plan.apply must not touch any host-side phase-1 machinery."""
    a, b = _case(seed=6)
    plans = [flexagon_plan(a, b, dataflow=d, block_shape=BS)
             for d in df.DATAFLOWS]

    def _forbidden(name):
        def fn(*args, **kwargs):
            raise AssertionError(f"{name} called during plan.apply")
        return fn

    for name in ("build_ip_plan", "build_op_plan", "build_gust_plan"):
        monkeypatch.setattr(df, name, _forbidden(name))
    monkeypatch.setattr(api, "select_dataflow",
                        _forbidden("select_dataflow"))
    monkeypatch.setattr(api.CompressionLayout, "from_bitmap",
                        _forbidden("CompressionLayout.from_bitmap"))

    before = dict(api.PHASE1_COUNTERS)
    ref = np.asarray(spmm_ref(a, b))
    for plan in plans:
        out = np.asarray(plan.apply(a, b))
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
    assert api.PHASE1_COUNTERS == before


def test_plan_reuse_same_pattern_new_values():
    """One plan serves any values sharing the sparsity pattern."""
    a, b = _case(seed=7)
    plan = flexagon_plan(a, b, block_shape=BS)
    before = dict(api.PHASE1_COUNTERS)
    for scale in (1.0, -2.5, 100.0):
        a2, b2 = a * scale, b * 0.5
        out = np.asarray(plan.apply(a2, b2))
        np.testing.assert_allclose(out, np.asarray(spmm_ref(a2, b2)),
                                   rtol=1e-4, atol=1e-4)
    assert api.PHASE1_COUNTERS == before
    assert plan.matches(a * 7.0, b)
    # a different pattern is NOT covered by this plan's fingerprint
    a_other, _ = _case(seed=99, da=0.15)
    assert not plan.matches(a_other, b)


def test_apply_under_jit_and_vjp_of_packed_operands():
    a, b = _case(seed=8)
    plan = flexagon_plan(a, b, block_shape=BS)
    ref = np.asarray(spmm_ref(a, b))
    jitted = jax.jit(plan.apply)
    out = np.asarray(jitted(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
    # packed operands (pytrees) flow through jit as arguments
    a_packed, b_packed = plan.pack_a(a), plan.pack_b(b)
    out2 = np.asarray(jax.jit(plan.apply)(a_packed, b_packed))
    np.testing.assert_allclose(out2, ref, rtol=1e-4, atol=1e-4)


def test_ingest_rejects_same_count_different_pattern():
    """An operand with the planned format and block count but *different*
    coordinates must be re-compressed, not fed to the frozen index plan."""
    rng = np.random.default_rng(20)
    a = np.zeros((16, 16), np.float32)
    a[:8, :8] = rng.standard_normal((8, 8))       # pattern P1: one block
    a2 = np.zeros((16, 16), np.float32)
    a2[8:, 8:] = rng.standard_normal((8, 8))      # pattern P2: one block too
    b = random_sparse_dense(rng, (16, 16), density=1.0, block_shape=(8, 8))

    plan = flexagon_plan(a, b, dataflow="gust_m", block_shape=BS)
    packed_other = flexagon_plan(a2, b, dataflow="gust_m",
                                 block_shape=BS).pack_a(a2)
    assert packed_other.nnzb == plan.a_layout.nnzb
    out = np.asarray(plan.apply(packed_other, b))
    # the mismatch is detected and the operand re-ingested under the plan's
    # pattern contract: off-pattern values drop (== dense-input semantics),
    # rather than being multiplied against the wrong index-plan partners.
    # a2 shares no blocks with the planned pattern, so C is exactly zero —
    # NOT the garbage a slot-mismatched gust work list would produce.
    np.testing.assert_array_equal(out, np.zeros_like(out))
    # dense input with the same off-pattern values agrees (one contract)
    np.testing.assert_array_equal(np.asarray(plan.apply(a2, b)), out)


def test_plan_pytree_roundtrip():
    a, b = _case(seed=9)
    plan = flexagon_plan(a, b, block_shape=BS, use_pallas=False)
    leaves, treedef = jax.tree_util.tree_flatten(plan)
    plan2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(plan2, FlexagonPlan)
    assert plan2.dataflow == plan.dataflow
    assert plan2.fingerprint == plan.fingerprint
    assert plan2.estimate == plan.estimate
    np.testing.assert_allclose(np.asarray(plan2.apply(a, b)),
                               np.asarray(spmm_ref(a, b)),
                               rtol=1e-4, atol=1e-4)


def test_plan_cache_hits():
    a, b = _case(seed=10)
    cache = PlanCache()
    p1 = cache.get(a, b, block_shape=BS)
    p2 = cache.get(a * 2.0, b * 3.0, block_shape=BS)   # same pattern
    assert p1 is p2
    assert cache.builds == 1 and cache.hits == 1
    a_other, _ = _case(seed=11, da=0.15)
    p3 = cache.get(a_other, b, block_shape=BS)
    assert p3 is not p1 and cache.builds == 2


# ---------------------------------------------------------------------------
# FlexagonPipeline
# ---------------------------------------------------------------------------


def test_pipeline_matches_dense_chain():
    rng = np.random.default_rng(12)
    tokens = 24
    ws = [random_sparse_dense(rng, (40, 32), density=0.5, block_shape=(8, 8)),
          random_sparse_dense(rng, (32, 24), density=0.3, block_shape=(8, 8)),
          random_sparse_dense(rng, (24, 16), density=0.8, block_shape=(8, 8))]
    pipe = FlexagonPipeline.from_weights(ws, tokens=tokens, block_shape=BS)
    x = rng.standard_normal((tokens, 40)).astype(np.float32)

    ref = x
    for w in ws:
        ref = ref @ w
    out = np.asarray(pipe.apply(x))
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)

    # Table 4 bookkeeping: majors follow the chosen dataflows, and legal
    # transitions are conversion-free
    assert pipe.majors == [df.OUTPUT_MAJOR[d] for d in pipe.dataflows]
    assert len(pipe.conversions) == len(ws) and not pipe.conversions[0]
    # jit the whole chain — no host-side work inside
    out_jit = np.asarray(jax.jit(pipe.apply)(jnp.asarray(x)))
    np.testing.assert_allclose(out_jit, ref, rtol=1e-3, atol=1e-3)


def test_pipeline_forced_dataflows_count_conversions():
    rng = np.random.default_rng(13)
    ws = [random_sparse_dense(rng, (16, 16), density=0.6, block_shape=(8, 8))
          for _ in range(2)]
    # ip_m emits CSR; op_n wants CSC-side input — Table 4 says EC
    pipe = FlexagonPipeline.from_weights(ws, tokens=16, block_shape=BS,
                                         dataflows=["ip_m", "op_n"])
    assert pipe.n_conversions == 1
    x = rng.standard_normal((16, 16)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(pipe.apply(x)), x @ ws[0] @ ws[1],
                               rtol=1e-3, atol=1e-3)


def test_pipeline_rejects_mismatched_chain():
    rng = np.random.default_rng(14)
    ws = [random_sparse_dense(rng, (16, 24), density=0.5, block_shape=(8, 8)),
          random_sparse_dense(rng, (16, 8), density=0.5, block_shape=(8, 8))]
    with pytest.raises(ValueError, match="previous layer"):
        FlexagonPipeline.from_weights(ws, tokens=8, block_shape=BS)


def test_pattern_fingerprint_stable_cross_process():
    """The pattern fingerprint (occupancy + shapes + block shape) must
    re-derive byte-identically in a fresh interpreter: it keys
    ``PlanCache`` and autotune's measurement cache, so instability would
    re-plan and re-measure patterns that were already seen."""
    from repro.api import _fingerprint

    rng = np.random.default_rng(0)
    occ_a = rng.random((7, 5)) < 0.4
    occ_b = rng.random((5, 9)) < 0.7
    local = _fingerprint(occ_a, occ_b, (112, 80, 144), (16, 16, 16))
    child = (
        "import numpy as np\n"
        "from repro.api import _fingerprint\n"
        "rng = np.random.default_rng(0)\n"
        "occ_a = rng.random((7, 5)) < 0.4\n"
        "occ_b = rng.random((5, 9)) < 0.7\n"
        "print(_fingerprint(occ_a, occ_b, (112, 80, 144), (16, 16, 16)))\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(repro.__file__))
    env["PYTHONHASHSEED"] = "999"
    proc = subprocess.run([sys.executable, "-c", child], check=True,
                          capture_output=True, text=True, env=env)
    assert proc.stdout.strip() == local
