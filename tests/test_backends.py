"""Backend/policy seam (repro.backends).

Contracts under test (DESIGN.md §11):

- registry: the three default substrates resolve by name; unknown names are
  a KeyError; custom backends register and serve plans end to end;
- capability negotiation: every default backend declares all six dataflows;
- parity: all six dataflows × reference/pallas agree numerically on shared
  patterns (same plan, re-targeted with ``with_backend``);
- policies: Heuristic matches ``select_dataflow`` and picks today's
  dataflow at every benchmark matmul shape; Simulator/Autotune return a
  legal dataflow deterministically for a fixed fingerprint (autotune
  measures once per fingerprint, in a bounded LRU, and leaves the backend
  as it found it); Fixed pins; ``get_policy`` resolves the three names,
  the six dataflow pins and nothing else;
- phase-1-once: ``plan.apply`` on the pallas backend leaves
  ``PHASE1_COUNTERS`` untouched;
- the interpret default centralizes in ``repro.config`` and follows the
  platform (CPU → interpret); an explicit argument wins;
- ``flexagon_spmm`` emits a real ``DeprecationWarning``.
"""
import jax
import numpy as np
import pytest

import repro.api as api
from repro import flexagon_plan, get_backend, get_policy
from repro.backends import (AutotunePolicy, BackendCapability,
                            ExecutionBackend, FixedPolicy, HeuristicPolicy,
                            SelectionContext, SimulatorPolicy, TABLE3_FORMATS,
                            allowed_dataflows, available_backends,
                            register_backend)
from repro.config import interpret_default, resolve_interpret
from repro.core import dataflows as df
from repro.core.formats import random_sparse_dense
from repro.core.selector import LayerShape, TPUSpec, select_dataflow

BS = (8, 8, 8)


def _case(seed=0, m=24, k=40, n=32, da=0.4, db=0.6):
    rng = np.random.default_rng(seed)
    a = random_sparse_dense(rng, (m, k), density=da, block_shape=(8, 8))
    b = random_sparse_dense(rng, (k, n), density=db, block_shape=(8, 8))
    return a, b


def _context(m=64, k=64, n=96, da=0.5, db=0.6, seed=0, budget=None,
             allowed=None, backend="reference", bs=(16, 16, 16)):
    """One SelectionContext on a seeded random block pattern."""
    be = get_backend(backend)
    rng = np.random.default_rng(seed)
    bm, bk, bn = bs
    occ_a = rng.random((m // bm, k // bk)) < da
    occ_b = rng.random((k // bk, n // bn)) < db
    occ_a[0, 0] = occ_b[0, 0] = True          # never a fully-empty operand
    shape = LayerShape(m, k, n, float(occ_a.mean()), float(occ_b.mean()),
                       block=bs)
    return SelectionContext(
        shape=shape, block_shape=bs, occ_a=occ_a, occ_b=occ_b,
        fingerprint=f"test:{m}x{k}x{n}:{da}:{db}:{seed}",
        backend=be, spec=TPUSpec(),
        allowed=tuple(allowed) if allowed else allowed_dataflows(be, bs),
        memory_budget=budget)


# ---------------------------------------------------------------------------
# Registry + capabilities
# ---------------------------------------------------------------------------


def test_default_backends_registered():
    assert {"reference", "pallas", "simulator"} <= set(available_backends())
    with pytest.raises(KeyError, match="unknown backend"):
        get_backend("does-not-exist")


@pytest.mark.parametrize("name", ["reference", "pallas", "simulator"])
def test_capability_declares_all_six(name):
    be = get_backend(name)
    for d in df.DATAFLOWS:
        assert be.supports(d, *TABLE3_FORMATS[d], BS)


def test_custom_backend_roundtrip():
    """A user-registered backend serves plans through the same surface."""

    class Doubling(ExecutionBackend):
        name = "test-doubling"

        def capabilities(self):
            return BackendCapability(dataflows=tuple(df.DATAFLOWS),
                                     formats=tuple(set(
                                         TABLE3_FORMATS.values())))

        def execute(self, plan, a, b, out_dtype):
            ref = get_backend("reference")
            return 2.0 * ref.execute(plan, a, b, out_dtype)

    register_backend(Doubling(), overwrite=True)
    a, b = _case()
    plan = flexagon_plan(a, b, block_shape=BS, backend="test-doubling")
    np.testing.assert_allclose(np.asarray(plan.apply(a, b)), 2.0 * (a @ b),
                               rtol=1e-4, atol=1e-4)


def test_get_backend_rejects_name_collision():
    """Passing a fresh instance under a taken name must not silently
    re-target every plan that resolves that name."""
    from repro.backends import PallasBackend

    with pytest.raises(ValueError, match="already registered"):
        get_backend(PallasBackend(interpret=False))


def test_with_backend_checks_capability():
    class IPOnly(ExecutionBackend):
        name = "test-ip-only"

        def capabilities(self):
            return BackendCapability(
                dataflows=("ip_m",),
                formats=tuple(set(TABLE3_FORMATS.values())))

        def execute(self, plan, a, b, out_dtype):
            return get_backend("reference").execute(plan, a, b, out_dtype)

    register_backend(IPOnly(), overwrite=True)
    a, b = _case(seed=20)
    plan = flexagon_plan(a, b, dataflow="gust_m", block_shape=BS)
    with pytest.raises(ValueError, match="does not support"):
        plan.with_backend("test-ip-only")
    # and phase-1 negotiation only offers the declared dataflow
    assert flexagon_plan(a, b, block_shape=BS,
                         backend="test-ip-only").dataflow == "ip_m"


# ---------------------------------------------------------------------------
# Cross-backend parity: six dataflows, shared pattern, identical results
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dataflow", df.DATAFLOWS)
def test_reference_pallas_parity(dataflow):
    a, b = _case(seed=3, m=16, k=24, n=16)
    ref_out = None
    for backend in ("reference", "pallas"):
        plan = flexagon_plan(a, b, dataflow=dataflow, block_shape=BS,
                             backend=backend)
        assert plan.backend == backend
        out = np.asarray(plan.apply(a, b))
        np.testing.assert_allclose(out, a @ b, rtol=1e-4, atol=1e-4)
        if ref_out is None:
            ref_out = out
        else:
            np.testing.assert_allclose(out, ref_out, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dataflow", ["gust_m", "op_n"])
def test_with_backend_retargets(dataflow):
    """One phase-1 run serves both substrates: only aux is rebuilt."""
    a, b = _case(seed=4)
    plan = flexagon_plan(a, b, dataflow=dataflow, block_shape=BS,
                         backend="reference")
    plan_p = plan.with_backend("pallas")
    assert plan_p.backend == "pallas" and plan_p.dataflow == plan.dataflow
    assert plan_p.a_layout is plan.a_layout
    np.testing.assert_allclose(np.asarray(plan_p.apply(a, b)),
                               np.asarray(plan.apply(a, b)),
                               rtol=1e-5, atol=1e-5)


def test_pallas_apply_does_not_replan():
    a, b = _case(seed=5, m=16, k=24, n=16)
    plans = [flexagon_plan(a, b, dataflow=d, block_shape=BS,
                           backend="pallas") for d in df.DATAFLOWS]
    before = dict(api.PHASE1_COUNTERS)
    for plan in plans:
        np.asarray(plan.apply(a, b))
    assert api.PHASE1_COUNTERS == before


def test_plan_pytree_roundtrip_pallas_backend():
    a, b = _case(seed=6, m=16, k=24, n=16)
    plan = flexagon_plan(a, b, dataflow="op_m", block_shape=BS,
                         backend="pallas")
    leaves, treedef = jax.tree_util.tree_flatten(plan)
    plan2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert plan2.backend == "pallas"
    np.testing.assert_allclose(np.asarray(plan2.apply(a, b)), a @ b,
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Selection policies
# ---------------------------------------------------------------------------


def test_heuristic_policy_matches_selector():
    a, b = _case(seed=7)
    plan = flexagon_plan(a, b, block_shape=BS, policy="heuristic")
    shape = LayerShape(m=24, k=40, n=32,
                       density_a=plan.a_layout.nnzb / (3 * 5),
                       density_b=plan.b_layout.nnzb / (5 * 4), block=BS)
    assert plan.dataflow == select_dataflow(shape, TPUSpec())


#: the benchmark cells' matmuls, tokens x d x ff (gate and up) and tokens x
#: ff x d (down), at 128-blocks with a dense A and 25% of B's blocks kept
_CELL_FAMILIES = [(16, 4096, 14336), (1024, 4096, 14336), (32, 8192, 22016),
                  (1024, 7168, 18432), (1024, 7168, 2048)]
_CELL_MATMULS = [mkn for m, d, f in _CELL_FAMILIES
                 for mkn in ((m, d, f), (m, f, d))]


@pytest.mark.parametrize("m,k,n", _CELL_MATMULS)
def test_heuristic_choice_at_benchmark_shapes(m, k, n):
    """Pins the dataflow each benchmark matmul runs: the DeepSeek-V3 dense
    layer's gate and up take ``ip_n``, every other matmul ``ip_m``."""
    block = (128, 128, 128)
    shape = LayerShape(m=m, k=k, n=n, density_a=1.0, density_b=0.25,
                       block=block)
    want = "ip_n" if (m, k, n) == (1024, 7168, 18432) else "ip_m"
    assert select_dataflow(shape) == want
    be = get_backend("pallas")
    mb, kb, nb = shape.grid
    occ_b = np.zeros((kb, nb), dtype=bool)
    occ_b.flat[::4] = True
    ctx = SelectionContext(
        shape=shape, block_shape=block, occ_a=np.ones((mb, kb), dtype=bool),
        occ_b=occ_b, fingerprint=f"cell:{m}x{k}x{n}", backend=be,
        spec=TPUSpec(), allowed=allowed_dataflows(be, block))
    assert HeuristicPolicy().select(ctx) == want


def test_fixed_policy_pins():
    a, b = _case(seed=8)
    plan = flexagon_plan(a, b, block_shape=BS, policy=FixedPolicy("op_n"))
    assert plan.dataflow == "op_n"
    # a dataflow name as the policy string is shorthand for the same pin
    assert flexagon_plan(a, b, block_shape=BS,
                         policy="gust_m").dataflow == "gust_m"
    # an explicit dataflow= wins over any policy
    assert flexagon_plan(a, b, dataflow="ip_m", block_shape=BS,
                         policy="autotune").dataflow == "ip_m"


def test_simulator_policy_legal_and_deterministic():
    a, b = _case(seed=9)
    picks = {flexagon_plan(a, b, block_shape=BS,
                           policy="simulator").dataflow for _ in range(3)}
    assert len(picks) == 1 and picks.pop() in df.DATAFLOWS


def test_autotune_policy_caches_by_fingerprint():
    a, b = _case(seed=10, m=16, k=16, n=16)
    pol = AutotunePolicy(reps=1)
    d1 = flexagon_plan(a, b, block_shape=BS, policy=pol).dataflow
    assert d1 in df.DATAFLOWS
    assert pol.measurements == 1
    # same pattern (new values): cache hit, same deterministic answer
    d2 = flexagon_plan(a * 2.0, b * 0.5, block_shape=BS, policy=pol).dataflow
    assert d2 == d1 and pol.measurements == 1
    # different pattern: a fresh sweep
    a2, _ = _case(seed=11, m=16, k=16, n=16, da=0.9)
    flexagon_plan(a2, b, block_shape=BS, policy=pol)
    assert pol.measurements == 2


def test_autotune_lru_bounded_and_counted():
    pol = AutotunePolicy(reps=1, maxsize=2)
    ctxs = [_context(m=32, k=32, n=32, seed=s, allowed=("ip_m", "gust_m"))
            for s in range(3)]
    for ctx in ctxs:
        pol.select(ctx)
    assert pol.measurements == 3 and pol.misses == 3
    assert pol.evictions == 1 and pol.stats["size"] == 2
    pol.select(ctxs[2])                       # still resident
    assert pol.hits == 1 and pol.measurements == 3
    pol.select(ctxs[0])                       # evicted: re-measured
    assert pol.measurements == 4
    stats = pol.stats
    assert stats["name"] == "autotune" and stats["maxsize"] == 2
    assert {"hits", "misses", "measurements", "evictions"} <= stats.keys()


def test_autotune_maxsize_validation():
    with pytest.raises(ValueError):
        AutotunePolicy(maxsize=0)
    AutotunePolicy(maxsize=None)              # unbounded is explicit, fine


def test_select_for_shape_fingerprint_block_and_dtype():
    """The shape-only fingerprint must split on block shape and dtype:
    the same logical shape at two element widths measures differently."""
    pol = AutotunePolicy(reps=1)
    s16 = LayerShape(32, 32, 32, 1.0, 1.0, block=(16, 16, 16))
    pol.select_for_shape(s16)
    pol.select_for_shape(s16)                       # cache hit
    assert pol.measurements == 1 and pol.hits == 1
    pol.select_for_shape(s16, dtype="bfloat16")     # new key: dtype
    assert pol.measurements == 2
    s32 = LayerShape(32, 32, 32, 1.0, 1.0, block=(32, 32, 32))
    pol.select_for_shape(s32)                       # new key: block shape
    assert pol.measurements == 3


def test_autotune_leaves_the_backend_untouched():
    """Measuring runs the registered pallas backend as every other plan
    does: the attributes it finds are the attributes it leaves, so a
    heuristic plan built after the select escapes to dense exactly as one
    built before it."""
    be = get_backend("pallas")
    before = dict(vars(be))
    # work ratio 0.35: sparse at the module threshold, dense at a lower one
    a2, b2 = _case(seed=14, m=16, k=40, n=32, da=1.0, db=0.4)
    keys_before = set(flexagon_plan(a2, b2, block_shape=BS,
                                    backend="pallas").aux)
    ctx = _context(m=16, k=16, n=16, seed=15, backend="pallas", bs=BS)
    assert AutotunePolicy(reps=1).select(ctx) in ctx.allowed
    assert vars(be) == before
    keys_after = set(flexagon_plan(a2, b2, block_shape=BS,
                                   backend="pallas").aux)
    assert keys_after == keys_before


@pytest.mark.parametrize("name", [None, "heuristic", "simulator", "autotune",
                                  *df.DATAFLOWS, "learned"])
def test_get_policy_resolves(name):
    """The three named policies, a fixed pin per dataflow, nothing else."""
    named = {None: HeuristicPolicy, "heuristic": HeuristicPolicy,
             "simulator": SimulatorPolicy, "autotune": AutotunePolicy}
    if name in named:
        assert type(get_policy(name)) is named[name]
    elif name in df.DATAFLOWS:
        pol = get_policy(name)
        assert isinstance(pol, FixedPolicy) and pol.dataflow == name
    else:
        with pytest.raises(KeyError, match="unknown policy"):
            get_policy(name)


def test_named_policies_are_singletons():
    assert get_policy("autotune") is get_policy("autotune")
    assert isinstance(get_policy(None), HeuristicPolicy)
    assert isinstance(get_policy("simulator"), SimulatorPolicy)
    with pytest.raises(KeyError, match="unknown policy"):
        get_policy("nope")


def test_simulator_backend_cost_and_report():
    be = get_backend("simulator")
    shape = LayerShape(m=64, k=64, n=64, density_a=0.3, density_b=0.5)
    costs = {d: be.cost(shape, d) for d in df.DATAFLOWS}
    assert all(c > 0 for c in costs.values())
    a, b = _case(seed=12)
    plan = flexagon_plan(a, b, block_shape=BS, backend="simulator")
    res = be.report(plan)
    assert res.cycles > 0 and res.dataflow.endswith("_m")


# ---------------------------------------------------------------------------
# Interpret knob + deprecation
# ---------------------------------------------------------------------------


def test_interpret_knob_centralized(monkeypatch):
    from repro.backends import PallasBackend

    plan = flexagon_plan(*_case(), block_shape=BS, backend="pallas")
    # the default follows the platform: the CPU interprets ...
    assert jax.default_backend() == "cpu"
    assert interpret_default() is True
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    # ... any accelerator compiles natively; an explicit argument still wins
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_default() is False
    assert resolve_interpret(None) is False
    assert resolve_interpret(True) is True
    assert PallasBackend()._interpret(plan) is False
    assert PallasBackend(interpret=True)._interpret(plan) is True


def test_flexagon_spmm_warns_deprecated():
    from repro.kernels import flexagon_spmm

    a, b = _case(seed=13, m=16, k=16, n=16)
    with pytest.warns(DeprecationWarning, match="re-plans on every call"):
        out, chosen = flexagon_spmm(a, b, block_shape=BS, use_pallas=False)
    assert chosen in df.DATAFLOWS
    np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-4, atol=1e-4)
