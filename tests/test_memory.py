"""repro.memory — tiled out-of-core execution (DESIGN.md §12).

The contract under test: when a pattern's working set exceeds the
``MemoryBudget``, phase 1 tiles the operation (≥ 2 tiles), ``TiledPlan.
apply`` matches the untiled reference for all six dataflows with zero
host-side plan work, the simulator backend reports per-tier traffic, and
the traffic-aware policies consume those numbers when ranking dataflows.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import repro.api as api
from repro import (FlexagonPipeline, MemoryBudget, PlanCache, SparseOperand,
                   TiledPlan, flexagon_plan, get_backend)
from repro.core import dataflows as df
from repro.core.formats import block_occupancy, random_sparse_dense
from repro.core.selector import LayerShape, plan_network
from repro.core.simulator.config import PAPER_CONFIG
from repro.memory import (TiledSimReport, schedule, tiled_estimate,
                          tiled_traffic)

BS = (8, 8, 8)

#: Small enough that the default test case tiles on every dataflow.
SMALL = MemoryBudget(l1_bytes=4096, l2_bytes=8192)
TINY = MemoryBudget(l1_bytes=1024, l2_bytes=2048)
HUGE = MemoryBudget(l1_bytes=1 << 30, l2_bytes=1 << 30)


def _case(seed=0, m=48, k=64, n=40, da=0.5, db=0.6):
    rng = np.random.default_rng(seed)
    a = random_sparse_dense(rng, (m, k), density=da, block_shape=BS[:2])
    b = random_sparse_dense(rng, (k, n), density=db, block_shape=BS[1:])
    return a, b


# ---------------------------------------------------------------------------
# MemoryBudget + schedulers
# ---------------------------------------------------------------------------


def test_budget_validation_and_views():
    with pytest.raises(ValueError, match="positive"):
        MemoryBudget(l1_bytes=0)
    big = SMALL.scaled(2.0)
    assert big.l1_bytes == 2 * SMALL.l1_bytes
    paper = MemoryBudget.from_accelerator(PAPER_CONFIG)
    assert paper.l2_bytes == PAPER_CONFIG.str_cache_bytes


@pytest.mark.parametrize("dataflow", df.DATAFLOWS)
def test_scheduler_tile_counts_track_budget(dataflow):
    a, b = _case(seed=1)
    occ_a = block_occupancy(a, BS[:2])
    occ_b = block_occupancy(b, BS[1:])

    one, _ = schedule(dataflow, occ_a, occ_b, BS, HUGE)
    some, _ = schedule(dataflow, occ_a, occ_b, BS, SMALL)
    many, _ = schedule(dataflow, occ_a, occ_b, BS, TINY)
    assert len(one) == 1
    assert len(some) >= 2
    assert len(many) >= len(some)

    # tiles cover the whole block grid (every (i, k, j) cell in some tile)
    mb, kb = occ_a.shape
    nb = occ_b.shape[1]
    covered = np.zeros((mb, kb, nb), dtype=bool)
    for t in many:
        covered[t.i0:t.i1, t.k0:t.k1, t.j0:t.j1] = True
    assert covered.all()


def test_op_scan_handles_non_divisible_k_grid():
    # kb = 5 blocks does not divide into 2 slabs evenly: the last slab
    # overhangs the grid (empty fibers) so extents stay scan-uniform
    a, b = _case(seed=20, m=32, k=40, n=32, da=0.9, db=0.9)
    plan = flexagon_plan(a, b, dataflow="op_m", block_shape=BS,
                         memory_budget=MemoryBudget(l1_bytes=3000,
                                                    l2_bytes=3000))
    assert isinstance(plan, TiledPlan) and plan.n_tiles >= 2
    assert len({t.k1 - t.k0 for t in plan.tiles}) == 1
    assert plan.scan_ok
    out = np.asarray(plan.apply(a, b))
    np.testing.assert_allclose(out, a @ b, rtol=1e-3, atol=1e-3)
    out_jit = np.asarray(jax.jit(plan.apply)(a, b))
    np.testing.assert_allclose(out_jit, a @ b, rtol=1e-3, atol=1e-3)


def test_ip_splits_columns_when_rows_exhausted():
    # one block row of A (M cannot split) but a wide C tile: the L1
    # overflow must fall through to an N split, not give up untiled
    rng = np.random.default_rng(21)
    a = rng.standard_normal((8, 32)).astype(np.float32)
    b = random_sparse_dense(rng, (32, 256), density=0.9, block_shape=BS[1:])
    plan = flexagon_plan(a, b, dataflow="ip_m", block_shape=BS,
                         memory_budget=MemoryBudget(l1_bytes=4096,
                                                    l2_bytes=1 << 20))
    assert isinstance(plan, TiledPlan) and plan.n_tiles >= 2
    assert all(t.i0 == 0 and t.i1 == 1 for t in plan.tiles)
    np.testing.assert_allclose(np.asarray(plan.apply(a, b)), a @ b,
                               rtol=1e-3, atol=1e-3)


def test_op_slabs_are_uniform_extent():
    a, b = _case(seed=2)
    occ_a = block_occupancy(a, BS[:2])
    occ_b = block_occupancy(b, BS[1:])
    tiles, merge = schedule("op_m", occ_a, occ_b, BS, TINY)
    extents = {t.k1 - t.k0 for t in tiles}
    assert len(extents) == 1           # uniform (scan-stackable) slabs
    # all slabs merge into the single whole-C region
    assert merge.n_regions == 1
    assert merge.max_contributions == len(tiles)


# ---------------------------------------------------------------------------
# Tiled-vs-untiled numerical parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dataflow", df.DATAFLOWS)
@pytest.mark.parametrize("fmt", ["bcsr", "bcsc"])
def test_tiled_matches_untiled_all_dataflows(dataflow, fmt):
    a, b = _case(seed=3)
    a_op = SparseOperand.from_dense(a, format=fmt, block_shape=BS[:2])
    untiled = flexagon_plan(a, b, dataflow=dataflow, block_shape=BS)
    ref = np.asarray(untiled.apply(a, b))

    plan = flexagon_plan(a_op, b, dataflow=dataflow, block_shape=BS,
                         memory_budget=SMALL)
    assert isinstance(plan, TiledPlan)
    assert plan.n_tiles >= 2
    assert plan.out_major == df.OUTPUT_MAJOR[dataflow]
    out = np.asarray(plan.apply(a_op, b))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out, a @ b, rtol=1e-3, atol=1e-3)
    # jit the whole tiled apply (scan path included for OP)
    out_jit = np.asarray(jax.jit(plan.apply)(jnp.asarray(a),
                                             jnp.asarray(b)))
    np.testing.assert_allclose(out_jit, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("budget,lo,hi", [
    (HUGE, 1, 1),
    (MemoryBudget(l1_bytes=3500, l2_bytes=16384), 2, 4),
    (TINY, 4, 1_000),
])
def test_budget_forces_one_two_many_tiles(budget, lo, hi):
    a, b = _case(seed=4)
    plan = flexagon_plan(a, b, dataflow="gust_m", block_shape=BS,
                         memory_budget=budget)
    n = plan.n_tiles if isinstance(plan, TiledPlan) else 1
    assert lo <= n <= hi
    out = np.asarray(plan.apply(a, b))
    np.testing.assert_allclose(out, a @ b, rtol=1e-3, atol=1e-3)


@settings(max_examples=6, deadline=None)
@given(st.sampled_from(df.DATAFLOWS),
       st.floats(min_value=0.15, max_value=0.9),
       st.floats(min_value=0.15, max_value=0.9),
       st.sampled_from([1024, 4096, 16384]))
def test_tiled_parity_property(dataflow, da, db, l1):
    a, b = _case(seed=int(da * 1e4) + int(db * 1e3), m=32, k=40, n=24,
                 da=da, db=db)
    budget = MemoryBudget(l1_bytes=l1, l2_bytes=2 * l1)
    plan = flexagon_plan(a, b, dataflow=dataflow, block_shape=BS,
                         memory_budget=budget)
    out = np.asarray(plan.apply(a, b))
    np.testing.assert_allclose(out, a @ b, rtol=1e-3, atol=1e-3)
    # same pattern, new values — the tiled plan is reusable like any plan
    out2 = np.asarray(plan.apply(a * -1.5, b * 0.5))
    np.testing.assert_allclose(out2, (a * -1.5) @ (b * 0.5),
                               rtol=1e-3, atol=1e-3)


def test_tiled_apply_does_zero_host_work(monkeypatch):
    """TiledPlan.apply must not touch any phase-1 machinery (counters and
    monkeypatched builders agree)."""
    a, b = _case(seed=5)
    plans = [flexagon_plan(a, b, dataflow=d, block_shape=BS,
                           memory_budget=SMALL) for d in df.DATAFLOWS]
    assert all(isinstance(p, TiledPlan) for p in plans)

    def _forbidden(name):
        def fn(*args, **kwargs):
            raise AssertionError(f"{name} called during TiledPlan.apply")
        return fn

    for name in ("build_ip_plan", "build_op_plan", "build_gust_plan"):
        monkeypatch.setattr(df, name, _forbidden(name))
    monkeypatch.setattr(api, "select_dataflow",
                        _forbidden("select_dataflow"))
    monkeypatch.setattr(api.CompressionLayout, "from_bitmap",
                        _forbidden("CompressionLayout.from_bitmap"))

    before = dict(api.PHASE1_COUNTERS)
    ref = a @ b
    for plan in plans:
        out = np.asarray(plan.apply(a, b))
        np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)
        out_jit = np.asarray(jax.jit(plan.apply)(a, b))
        np.testing.assert_allclose(out_jit, ref, rtol=1e-3, atol=1e-3)
    assert api.PHASE1_COUNTERS == before


def test_tiled_plan_pytree_roundtrip_and_matches():
    a, b = _case(seed=6)
    plan = flexagon_plan(a, b, dataflow="op_m", block_shape=BS,
                         memory_budget=SMALL)
    leaves, treedef = jax.tree_util.tree_flatten(plan)
    plan2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(plan2, TiledPlan)
    assert plan2.n_tiles == plan.n_tiles
    assert plan2.fingerprint == plan.fingerprint
    np.testing.assert_array_equal(plan2.occ_a, plan.occ_a)
    np.testing.assert_allclose(np.asarray(plan2.apply(a, b)), a @ b,
                               rtol=1e-3, atol=1e-3)
    assert plan.matches(a * 3.0, b)
    a_other, _ = _case(seed=60, da=0.15)
    assert not plan.matches(a_other, b)


# ---------------------------------------------------------------------------
# Backends: scan streaming + retargeting
# ---------------------------------------------------------------------------


def test_op_scan_streaming_and_backend_retarget():
    a, b = _case(seed=7)
    plan = flexagon_plan(a, b, dataflow="op_m", block_shape=BS,
                         memory_budget=SMALL)
    assert plan.scan_ok and get_backend("reference").scan_streaming
    ref = np.asarray(plan.apply(a, b))

    # pallas scans stacked (traced) StreamSchedules too: retargeting keeps
    # the tiling and the scan path, numerics unchanged
    on_pallas = plan.with_backend("pallas")
    assert on_pallas.backend == "pallas" and on_pallas.scan_ok
    np.testing.assert_allclose(np.asarray(on_pallas.apply(a, b)), ref,
                               rtol=1e-4, atol=1e-4)
    back = on_pallas.with_backend("reference")
    assert back.scan_ok
    np.testing.assert_allclose(np.asarray(back.apply(a, b)), ref,
                               rtol=1e-4, atol=1e-4)


def test_tiled_plan_built_on_pallas_backend():
    a, b = _case(seed=8, m=24, k=32, n=16)
    plan = flexagon_plan(a, b, dataflow="gust_m", block_shape=BS,
                         backend="pallas", memory_budget=TINY)
    assert isinstance(plan, TiledPlan) and plan.n_tiles >= 2
    # a per-band StreamSchedule was prepared for every tile sub-plan
    assert all("stream_schedule" in (p.aux or {}) for p in plan.plans)
    np.testing.assert_allclose(np.asarray(plan.apply(a, b)), a @ b,
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Traffic: simulator report + traffic-aware policies
# ---------------------------------------------------------------------------


def test_simulator_report_shows_per_tier_traffic():
    a, b = _case(seed=9)
    be = get_backend("simulator")
    plan = flexagon_plan(a, b, dataflow="op_m", block_shape=BS,
                         backend="simulator", memory_budget=SMALL)
    rep = be.report(plan)
    assert isinstance(rep, TiledSimReport)
    assert rep.n_tiles == plan.n_tiles >= 2
    t = rep.traffic
    assert t.l1_bytes > 0 and t.l2_bytes > 0 and t.dram_bytes > 0
    assert t.merge_bytes > 0                 # k-slabs merge partial C
    assert t.cycles > 0 and t.time_s() > 0
    assert t.onchip_bytes == t.l1_bytes + t.l2_bytes
    # untiled plans keep the classic SimResult report
    small = flexagon_plan(a, b, dataflow="op_m", block_shape=BS,
                          backend="simulator")
    assert be.report(small).cycles > 0


def test_policies_consume_tiled_traffic():
    a, b = _case(seed=10)
    occ_a = block_occupancy(a, BS[:2])
    occ_b = block_occupancy(b, BS[1:])
    cfg = get_backend("simulator").cfg

    # the simulator policy's budgeted choice is the argmin of exactly the
    # traffic numbers the report exposes
    expect = min(df.DATAFLOWS, key=lambda d: (
        tiled_traffic(d, occ_a, occ_b, BS, SMALL, cfg).time_s(cfg), d))
    p1 = flexagon_plan(a, b, block_shape=BS, policy="simulator",
                       memory_budget=SMALL)
    p2 = flexagon_plan(a, b, block_shape=BS, policy="simulator",
                       memory_budget=SMALL)
    assert p1.dataflow == p2.dataflow == expect

    # heuristic ranks by the analytic tiled estimate
    h = flexagon_plan(a, b, block_shape=BS, policy="heuristic",
                      memory_budget=SMALL)
    shape = LayerShape(a.shape[0], a.shape[1], b.shape[1],
                       float(occ_a.mean()), float(occ_b.mean()), BS)
    expect_h = min(df.DATAFLOWS, key=lambda d: (
        tiled_estimate(shape, d, SMALL, occ_a=occ_a,
                       occ_b=occ_b).time_s, d))
    assert h.dataflow == expect_h


def test_plan_network_threads_budget():
    layers = [LayerShape(m=64, k=512, n=512, density_a=1.0, density_b=0.4,
                         block=BS),
              LayerShape(m=64, k=512, n=256, density_a=1.0, density_b=0.6,
                         block=BS)]
    seq = plan_network(layers, memory_budget=SMALL)
    assert len(seq) == 2 and all(d in df.DATAFLOWS for d in seq)


def test_pipeline_threads_budget():
    rng = np.random.default_rng(11)
    ws = [random_sparse_dense(rng, (40, 32), density=0.5, block_shape=BS[:2]),
          random_sparse_dense(rng, (32, 24), density=0.6, block_shape=BS[:2])]
    pipe = FlexagonPipeline.from_weights(ws, tokens=48, block_shape=BS,
                                         memory_budget=TINY)
    assert any(isinstance(p, TiledPlan) for p in pipe.plans)
    x = rng.standard_normal((48, 40)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(pipe.apply(x)), x @ ws[0] @ ws[1],
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# PlanCache LRU + serving counters
# ---------------------------------------------------------------------------


def test_plan_cache_lru_counters_and_eviction():
    cache = PlanCache(maxsize=2)
    a, b = _case(seed=12, m=16, k=16, n=16)
    p1 = cache.get(a, b, block_shape=BS)
    assert cache.get(a * 2.0, b, block_shape=BS) is p1
    assert cache.stats == {"hits": 1, "misses": 1, "evictions": 0,
                           "size": 1, "maxsize": 2}
    patterns = [_case(seed=s, m=16, k=16, n=16, da=da)[0]
                for s, da in ((13, 0.25), (14, 0.45))]
    for ap in patterns:
        cache.get(ap, b, block_shape=BS)
    assert len(cache) == 2 and cache.evictions == 1
    assert cache.misses == cache.builds == 3
    # the evicted (oldest) pattern rebuilds; the survivors still hit
    hits_before = cache.hits
    cache.get(patterns[-1], b, block_shape=BS)
    assert cache.hits == hits_before + 1
    cache.get(a, b, block_shape=BS)            # was evicted -> rebuild
    assert cache.builds == 4 and cache.evictions == 2
    with pytest.raises(ValueError, match="maxsize"):
        PlanCache(maxsize=0)
    # budgeted and unbudgeted plans are distinct cache entries
    cache2 = PlanCache()
    q1 = cache2.get(a, b, block_shape=BS)
    q2 = cache2.get(a, b, block_shape=BS, memory_budget=HUGE)
    assert q1 is not q2 and cache2.builds == 2


def test_compressed_ffn_bounded_shape_cache():
    from repro.models.sparse_linear import CompressedFFN

    rng = np.random.default_rng(15)
    d, f = 32, 48
    wg = random_sparse_dense(rng, (d, f), density=0.5, block_shape=BS[:2])
    wu = random_sparse_dense(rng, (d, f), density=0.5, block_shape=BS[:2])
    wd = random_sparse_dense(rng, (f, d), density=0.5, block_shape=BS[:2])
    comp = CompressedFFN(wg, wu, wd, tokens=8, block=8, max_shapes=2)
    assert comp.plan_builds == 1
    comp.specialize(8)
    assert comp.plan_hits == 1
    for t in (16, 24, 40):                     # overflow the shape cache
        comp.specialize(t)
    assert comp.shape_evictions >= 2
    stats = comp.cache_stats
    for key in ("hits", "misses", "evictions", "shapes", "shape_evictions"):
        assert key in stats
    assert stats["shapes"] <= 2
    # the construction-time default shape replans transparently if evicted
    assert comp.dataflow_in in df.DATAFLOWS
