"""Quickstart: the paper in one script.

1. Build two sparse matrices, run C = A @ B through all six SpMSpM dataflows
   on both execution backends — `reference` (pure JAX) and `pallas` (TPU
   kernels, interpret mode on CPU) — everyone agrees with the dense oracle.
   `backend="pallas"` is the *fast path*: two fused streaming kernels over a
   shared StreamSchedule work list, jit-cached so even an unjitted serving
   loop replays compiled executables (DESIGN.md §18).
2. Plan once with the phase-1 mapper/compiler (`flexagon_plan`), execute many
   — including under `jax.jit` — swap selection policies (heuristic vs the
   cycle-level simulator), and chain layers with `FlexagonPipeline`.
3. Give the plan a `memory_budget` (the paper's 3-tier memory hierarchy):
   an over-budget pattern auto-tiles into a `TiledPlan`, and the simulator
   reports per-tier (L1/L2/DRAM) traffic for the tile stream.
4. Give the plan a `mesh`: phase 1 partitions it across the devices into a
   `ShardedPlan` (one `shard_map` apply; OP k-slabs merge partial sums with
   a psum collective, priced as an interconnect traffic tier).
5. Reproduce the paper's headline on one Table 6 layer with the cycle-level
   simulator: Flexagon == best of {SIGMA-like, SpArch-like, GAMMA-like}.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
from repro.config import virtual_devices

virtual_devices(8)      # 8 virtual CPU devices, before jax's backend init

import jax
import numpy as np

from repro import (FlexagonPipeline, MemoryBudget, ShardedPlan,
                   SparseOperand, TiledPlan, available_backends,
                   flexagon_plan, get_backend, get_policy)
from repro.launch.mesh import make_virtual_mesh
from repro.core import (DATAFLOWS, LayerShape, random_sparse_dense,
                        select_dataflow)
from repro.core.simulator import ACCELERATORS, from_layer, simulate
from repro.core.workloads import PAPER_LAYERS


def main():
    rng = np.random.default_rng(0)
    a = random_sparse_dense(rng, (64, 64), density=0.3, block_shape=(16, 16))
    b = random_sparse_dense(rng, (64, 96), density=0.6, block_shape=(16, 16))
    oracle = a @ b

    print(f"== six dataflows × two backends, one answer "
          f"(registry: {', '.join(available_backends())}) ==")
    for df in DATAFLOWS:
        errs = []
        for backend in ("reference", "pallas"):
            plan = flexagon_plan(a, b, dataflow=df, block_shape=(16, 16, 16),
                                 backend=backend)
            out = np.asarray(plan.apply(a, b))
            errs.append(f"{backend} {np.abs(out - oracle).max():.2e}")
        print(f"  {df:8s} max|err| = {' | '.join(errs)}")

    print("== plan once (phase 1), execute many (phase 2) ==")
    plan = flexagon_plan(a, b, block_shape=(16, 16, 16))
    print(f"  selector picked {plan.dataflow!r} "
          f"(est {plan.estimate.time_s * 1e9:.1f} ns on TPUSpec), "
          f"output major order {plan.out_major!r}, "
          f"backend {plan.backend!r}")
    print("== swap the selection policy (same plan surface) ==")
    for pname in ("heuristic", "simulator"):
        p = flexagon_plan(a, b, block_shape=(16, 16, 16), policy=pname)
        print(f"  policy {pname!r:12s} -> {p.dataflow}")
    autotuned = flexagon_plan(a, b, block_shape=(16, 16, 16),
                              policy=get_policy("autotune"))
    print(f"  policy 'autotune'  -> {autotuned.dataflow} "
          "(measured on-device, cached by pattern fingerprint)")
    out = np.asarray(plan.apply(a, b))
    print(f"  plan.apply          max|err| = {np.abs(out - oracle).max():.2e}")
    # same pattern, new values — no re-planning, and jit-compatible
    a2 = a * 3.0
    out2 = np.asarray(jax.jit(plan.apply)(a2, b))
    ref2 = a2 @ b
    print(f"  jit(plan.apply)     max|err| = {np.abs(out2 - ref2).max():.2e}")
    # operands can be packed once and reused too
    a_packed = plan.pack_a(a)
    assert isinstance(a_packed, SparseOperand)
    print(f"  packed A: {a_packed.fmt.value}, {a_packed.nnzb} blocks "
          f"(density {a_packed.density:.2f})")
    for name, spec in list(PAPER_LAYERS.items())[:3]:
        shape = LayerShape(spec.m, spec.k, spec.n,
                           spec.density_a, spec.density_b)
        print(f"  layer {name}: selector says {select_dataflow(shape)}")

    print("== plan_network pipeline (Table 4 transitions) ==")
    w1 = random_sparse_dense(rng, (96, 64), density=0.4, block_shape=(16, 16))
    w2 = random_sparse_dense(rng, (64, 32), density=0.6, block_shape=(16, 16))
    pipe = FlexagonPipeline.from_weights([b, w1, w2], tokens=64,
                                         block_shape=(16, 16, 16))
    x = rng.standard_normal((64, 64)).astype(np.float32)
    y = np.asarray(pipe.apply(x))
    ref = x @ b @ w1 @ w2
    print(f"  dataflows {pipe.dataflows}, majors {pipe.majors}, "
          f"{pipe.n_conversions} explicit conversions")
    print(f"  chain max|err| = {np.abs(y - ref).max():.2e}")

    print("== out-of-core: memory_budget tiles what doesn't fit on chip ==")
    # a toy 12 KiB chip: the pattern exceeds it, so phase 1 auto-tiles into
    # a TiledPlan (per-dataflow scheduler; OP k-slabs stream via lax.scan)
    budget = MemoryBudget(l1_bytes=4 << 10, l2_bytes=8 << 10)
    tiled = flexagon_plan(a, b, block_shape=(16, 16, 16),
                          memory_budget=budget)
    assert isinstance(tiled, TiledPlan)
    out_t = np.asarray(jax.jit(tiled.apply)(a, b))
    print(f"  {tiled.dataflow!r} in {tiled.n_tiles} tiles "
          f"(merge regions: {tiled.merge_plan.n_regions}), "
          f"max|err| = {np.abs(out_t - oracle).max():.2e}")
    rep = get_backend("simulator").report(tiled.with_backend("simulator"))
    t = rep.traffic
    print(f"  tier traffic: L1 {t.l1_bytes / 1e3:.0f} kB, "
          f"L2 {t.l2_bytes / 1e3:.0f} kB, DRAM {t.dram_bytes / 1e3:.0f} kB "
          f"(merge {t.merge_bytes / 1e3:.1f} kB) over {t.tiles} tiles")

    print("== mixed-dataflow tiles: dataflow becomes a per-tile decision ==")
    # heterogeneous pattern — a dense band + uniform-sparse remainder in A.
    # dataflow="mixed" tiles the output grid (disjoint C regions) and lets
    # the selection policy pick each tile's dataflow on the tile's own
    # occupancy slice; the simulator prices the mix at or below every
    # single-dataflow plan (DESIGN.md §14)
    ah = np.zeros((96, 96), np.float32)
    ah[:48] = rng.standard_normal((48, 96)).astype(np.float32)
    ah[48:] = random_sparse_dense(rng, (48, 96), density=0.5,
                                  block_shape=(8, 8))
    bh = random_sparse_dense(rng, (96, 96), density=0.9, block_shape=(8, 8))
    hbudget = MemoryBudget(l1_bytes=20000, l2_bytes=40000)
    mixed = flexagon_plan(ah, bh, dataflow="mixed", block_shape=(8, 8, 8),
                          memory_budget=hbudget, policy="simulator",
                          backend="simulator")
    assert isinstance(mixed, TiledPlan) and mixed.dataflow == "mixed"
    out_m = np.asarray(jax.jit(mixed.apply)(ah, bh))
    print(f"  per-tile choices over {mixed.n_tiles} tiles: "
          f"{mixed.tile_histogram}, "
          f"max|err| = {np.abs(out_m - ah @ bh).max():.2e}")
    sim_be = get_backend("simulator")
    mrep = sim_be.report(mixed)
    mixed_s = mrep.traffic.time_s(sim_be.cfg)
    singles = {}
    for d in DATAFLOWS:
        p = flexagon_plan(ah, bh, dataflow=d, block_shape=(8, 8, 8),
                          memory_budget=hbudget, backend="simulator")
        r = sim_be.report(p)
        singles[d] = r.traffic.time_s(sim_be.cfg) if isinstance(p, TiledPlan) \
            else r.cycles / sim_be.cfg.freq_hz
    best_d = min(singles, key=singles.get)
    print(f"  simulator pricing: mixed {mixed_s * 1e6:.2f} us <= best "
          f"single {best_d!r} {singles[best_d] * 1e6:.2f} us")
    assert mixed_s <= singles[best_d] * (1 + 1e-9)

    print("== observability: trace the plan lifecycle into Perfetto ==")
    # repro.obs (DESIGN.md §17): spans around phase 1 (select/tables/
    # prepare, per-tile choices) and every unjitted apply, counters +
    # latency histograms in the metrics registry.  Off by default
    # (REPRO_TRACE) — enable() flips it for this process.
    from repro import obs

    obs.enable()
    traced_plan = flexagon_plan(ah, bh, dataflow="mixed",
                                block_shape=(8, 8, 8),
                                memory_budget=hbudget, policy="simulator",
                                backend="simulator")
    for _ in range(10):                 # unjitted: one apply span per step
        np.asarray(traced_plan.apply(ah, bh))
    n = obs.get_tracer().save_chrome("quickstart_trace.json")
    reg = obs.get_registry()
    print(f"  {n} spans -> quickstart_trace.json "
          "(open at https://ui.perfetto.dev)")
    print(f"  metrics: plan.builds={reg.value('plan.builds'):.0f}, "
          f"select_tile p99 "
          f"{reg.get('policy.select_tile_s').quantile(0.99) * 1e6:.0f} us "
          f"over {reg.value('policy.select_tile_s'):.0f} tile choices")
    obs.disable()

    print("== distributed: mesh= partitions the plan across devices ==")
    # the dataflow's Partitioner shards the block grid (IP: output panels,
    # OP: k-slabs + psum merge, Gust: row bands); apply is one shard_map
    mesh = make_virtual_mesh(min(8, len(jax.devices())))
    sharded = flexagon_plan(a, b, dataflow="op_m", block_shape=(16, 16, 16),
                            mesh=mesh)
    assert isinstance(sharded, ShardedPlan)
    out_s = np.asarray(jax.jit(sharded.apply)(a, b))
    print(f"  {sharded.dataflow!r} over {sharded.n_shards} shards "
          f"(axis {sharded.axis!r}, collective {sharded.collective!r}), "
          f"max|err| = {np.abs(out_s - oracle).max():.2e}")
    rep = get_backend("simulator").report(sharded.with_backend("simulator"))
    print(f"  interconnect tier: {rep.traffic.ici_bytes / 1e3:.1f} kB "
          f"psum-merge traffic across {rep.shards} shards "
          f"(L1 {rep.traffic.l1_bytes / 1e3:.0f} kB, "
          f"DRAM {rep.traffic.dram_bytes / 1e3:.0f} kB)")

    print("== cycle-level simulator (paper layer V0) ==")
    st = from_layer(PAPER_LAYERS["V0"])
    cycles = {name: simulate(name, st).cycles for name in ACCELERATORS}
    for name, c in cycles.items():
        print(f"  {name:12s} {c:12.0f} cycles")
    best_fixed = min(v for k, v in cycles.items() if k != "flexagon")
    assert cycles["flexagon"] <= best_fixed * 1.001
    print("  => Flexagon matches the best fixed-dataflow accelerator.")


if __name__ == "__main__":
    main()
