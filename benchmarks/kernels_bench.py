"""Kernel microbenchmarks: plan-build vs steady-state apply, per backend.

Wall-clock here is CPU time (NOT TPU performance — the roofline story lives
in EXPERIMENTS.md §Roofline); what this bench establishes is correctness at
size and the phase split the plan API exists for, on every registered
execution substrate:

- ``plan_build`` — one-time phase-1 cost (occupancy, policy, layouts,
  index plans, backend prepare);
- ``plan_apply`` — steady-state phase-2 cost, the number that matters for a
  serving loop (and the ROADMAP perf trajectory);
- ``per_call``   — the seed-equivalent one-shot path (plan + apply on every
  invocation), which pays both.

``plan_apply`` must not exceed ``per_call`` on any (shape, backend)
(asserted).  Everything routes through the backend registry — no kernel
module is imported here.

Each (dataflow, backend) row also records the *memory behaviour* of the
operation under the paper's Table 5 on-chip budget (``repro.memory``):
estimated on-chip bytes (L1 + L2), off-chip bytes, and how many tiles the
dataflow's scheduler needs — so BENCH_kernels.json tracks traffic, not just
latency.  Each row's ``tile_dataflows`` field is *that plan's own* per-tile
dataflow histogram; the case's mixed-mode histogram (DESIGN.md §14) gets a
dedicated ``mixed_tiles`` row so heterogeneity trends stay visible without
mislabeling single-dataflow rows.  Rows additionally carry the *distributed* trajectory
(``repro.dist``): the virtual mesh shape, shard count, and interconnect
(ICI) bytes of the dataflow's partition strategy over ``DIST_SHARDS``
shards — nonzero for OP k-slabs, whose partial sums all-reduce across the
mesh.

CLI (the CI smoke step)::

    python -m benchmarks.kernels_bench --quick --json BENCH_kernels.json

``--verify`` additionally gates every (untimed) plan build behind
``repro.analysis.verify_plan`` — the timed ``plan_build``/``per_call``
lambdas stay unverified so latency rows remain comparable across runs.
``--trace out.json`` turns on ``repro.obs`` tracing for the whole run and
writes a Chrome-trace/Perfetto JSON of every phase-1/apply span.  Policy
rows report ``selection_latency_s`` as a summary over repeats
(count/mean/min/max/p50/p99), not a single draw.
"""
from __future__ import annotations

import argparse
import json
import time
from collections import Counter

import numpy as np

from repro import PAPER_BUDGET, flexagon_plan, get_policy
from repro.analysis import check_schedule, verify_plan
from repro.backends import SelectionContext, allowed_dataflows, get_backend
from repro.core import random_sparse_dense
from repro.core.formats import block_occupancy
from repro.core.dataflows import DATAFLOWS
from repro.core.selector import LayerShape, TPUSpec
from repro.memory import mixed_tile_choices, sharded_traffic, tiled_traffic
from .common import Row

BACKENDS = ("reference", "pallas")
BS = (16, 16, 16)
#: shard count for the analytic multi-device pricing (pattern-level, so no
#: actual devices are needed — the row tracks the trajectory, not wall-clock)
DIST_SHARDS = 4
CASES = [
    ("sq_like", 64, 64, 128, 0.3, 0.9),
    ("op_like", 64, 256, 64, 0.1, 0.5),
    ("gust_like", 128, 128, 64, 0.5, 0.2),
]


def _time(fn, reps=3):
    fn()  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    np.asarray(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run(quick: bool = False, verify: bool = False) -> list[Row]:
    rows = []
    rng = np.random.default_rng(7)
    cases = CASES[:1] if quick else CASES
    dataflows = ("ip_m", "op_m", "gust_m") if quick else DATAFLOWS
    reps = 1 if quick else 3
    for name, m, k, n, da, db in cases:
        a = random_sparse_dense(rng, (m, k), density=da, block_shape=BS[:2])
        b = random_sparse_dense(rng, (k, n), density=db, block_shape=BS[1:])
        ref = a @ b
        occ_a = block_occupancy(a, BS[:2])
        occ_b = block_occupancy(b, BS[1:])
        # memory behaviour per dataflow under the Table 5 on-chip budget
        # (backend-independent: the schedule depends on pattern + budget)
        memory = {
            df: tiled_traffic(df, occ_a, occ_b, BS, PAPER_BUDGET)
            for df in dataflows
        }
        # multi-device trajectory: the dataflow's partition strategy over a
        # virtual DIST_SHARDS-shard mesh, interconnect tier included
        dist = {
            df: sharded_traffic(df, occ_a, occ_b, BS, DIST_SHARDS,
                                budget=PAPER_BUDGET)
            for df in dataflows
        }
        # the mixed-mode trajectory (DESIGN.md §14): per-tile dataflow
        # histogram of the case's mixed schedule under the same budget —
        # reported on its own row (it describes the *mixed* schedule, not
        # any single-dataflow plan's tiles)
        mixed_hist = dict(Counter(
            mixed_tile_choices(occ_a, occ_b, BS, PAPER_BUDGET)))
        rows.append(Row(
            f"kernels/{name}/mixed_tiles", 0.0,
            " ".join(f"{d}={c}" for d, c in sorted(mixed_hist.items())),
            extra={"tile_dataflows": mixed_hist}))
        for backend in BACKENDS:
            # per-dataflow correctness + latency through the registry
            for df in dataflows:
                plan = flexagon_plan(a, b, dataflow=df, block_shape=BS,
                                     backend=backend, verify=verify or None)
                us = _time(lambda p=plan: p.apply(a, b), reps=reps)
                err = float(np.abs(np.asarray(plan.apply(a, b)) - ref).max())
                t = memory[df]
                d = dist[df]
                rows.append(Row(
                    f"kernels/{name}/{backend}/{df}", us,
                    f"max_err={err:.1e} onchip={t.onchip_bytes:.0f}B "
                    f"tiles={t.tiles} ici={d.ici_bytes:.0f}B",
                    extra={"onchip_bytes": t.onchip_bytes,
                           "l1_bytes": t.l1_bytes,
                           "l2_bytes": t.l2_bytes,
                           "dram_bytes": t.dram_bytes,
                           "tiles": t.tiles,
                           "mesh_shape": [DIST_SHARDS],
                           "shards": DIST_SHARDS,
                           "ici_bytes": d.ici_bytes,
                           # this row's own plan: a fixed-dataflow plan's
                           # tiles all run its dataflow (untiled -> one)
                           "tile_dataflows":
                               getattr(plan, "tile_histogram", None)
                               or {df: 1}}))

            # phase split: plan once (build) vs execute many (apply) vs the
            # seed-equivalent per-call path that pays both every time
            build_us = _time(
                lambda be=backend: flexagon_plan(a, b, block_shape=BS,
                                                 backend=be), reps=reps)
            plan = flexagon_plan(a, b, block_shape=BS, backend=backend,
                                 verify=verify or None)
            apply_us = _time(lambda: plan.apply(a, b), reps=max(reps, 2))
            per_call_us = _time(
                lambda be=backend: flexagon_plan(
                    a, b, block_shape=BS, backend=be).apply(a, b),
                reps=max(reps, 2))
            err = float(np.abs(np.asarray(plan.apply(a, b)) - ref).max())
            rows.append(Row(f"kernels/{name}/{backend}/plan_build", build_us,
                            f"dataflow={plan.dataflow}"))
            # static-analysis overhead (DESIGN.md §19): full verify_plan —
            # plan invariants + the schedule checker — on the built plan,
            # plus the schedule checker alone, both as fractions of
            # plan_build so the "checker costs <10% of planning" budget is
            # tracked as a bench trajectory, not an anecdote
            verify_us = _time(lambda: len(verify_plan(plan)),
                              reps=max(reps, 2))
            if getattr(plan, "aux", None) \
                    and "stream_schedule" in plan.aux:
                sched_us = _time(lambda: len(check_schedule(plan)),
                                 reps=max(reps, 2))
            else:
                sched_us = 0.0      # no aux schedule on this backend
            rows.append(Row(
                f"kernels/{name}/{backend}/plan_verify", verify_us,
                f"of_build={verify_us / build_us:.3f} "
                f"sched_of_build={sched_us / build_us:.3f}",
                extra={"verify_us": verify_us, "build_us": build_us,
                       "schedule_checker_us": sched_us,
                       "verify_over_build": verify_us / build_us,
                       "schedule_checker_over_build":
                           sched_us / build_us}))
            rows.append(Row(f"kernels/{name}/{backend}/plan_apply", apply_us,
                            f"max_err={err:.1e}"))
            rows.append(Row(f"kernels/{name}/{backend}/per_call", per_call_us,
                            "per-call plan+apply"))
            # 1.25x headroom so scheduler noise on a loaded box doesn't abort
            # the whole run; the reported rows carry the actual numbers
            assert apply_us <= per_call_us * 1.25, (
                f"{name}/{backend}: steady-state apply ({apply_us:.0f}us) "
                f"slower than per-call plan+apply ({per_call_us:.0f}us)")

        # selection policies, through the same seam the plans use; each row
        # carries which policy selected and how long its select() takes
        shape = LayerShape(m, k, n, float(occ_a.mean()), float(occ_b.mean()),
                           block=BS)
        ctx = SelectionContext(
            shape=shape, block_shape=BS, occ_a=occ_a, occ_b=occ_b,
            fingerprint=f"bench:{name}", backend=get_backend("reference"),
            spec=TPUSpec(), allowed=allowed_dataflows(
                get_backend("reference"), BS))
        sel_reps = 5 if quick else 15
        for pname in ("heuristic", "simulator"):
            pol = get_policy(pname)
            choice = pol.select(ctx)        # warmup (fills policy caches)
            # selection latency as a distribution, not a single draw: the
            # row reports p50/p99 over repeats (scheduler noise on shared
            # CI boxes makes one-shot numbers useless for trajectories)
            lats = []
            for _ in range(sel_reps):
                t0 = time.perf_counter()
                assert pol.select(ctx) == choice
                lats.append(time.perf_counter() - t0)
            sel = {"count": len(lats),
                   "mean": float(np.mean(lats)),
                   "min": float(np.min(lats)),
                   "max": float(np.max(lats)),
                   "p50": float(np.percentile(lats, 50)),
                   "p99": float(np.percentile(lats, 99))}
            plan = flexagon_plan(a, b, block_shape=BS, policy=pol)
            assert plan.dataflow == choice, (name, pname)
            rows.append(Row(f"kernels/{name}/policy_{pname}",
                            sel["p50"] * 1e6,
                            f"choice={plan.dataflow}",
                            extra={"policy": pname,
                                   "selection_latency_s": sel}))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="1 case, 3 dataflows, 1 rep (CI smoke)")
    ap.add_argument("--json", metavar="PATH",
                    help="also write rows as JSON (CI artifact)")
    ap.add_argument("--verify", action="store_true",
                    help="gate every built plan behind "
                         "repro.analysis.verify_plan (raises on error)")
    ap.add_argument("--trace", metavar="PATH",
                    help="capture a repro.obs span trace of the whole run "
                         "and write Chrome-trace/Perfetto JSON here")
    args = ap.parse_args()
    if args.trace:
        from repro import obs

        obs.enable()
    rows = run(quick=args.quick, verify=args.verify)
    print("name,us_per_call,derived")
    for row in rows:
        print(row.csv())
    if args.json:
        payload = {
            "bench": "kernels",
            "quick": args.quick,
            "rows": [r.json() for r in rows],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}")
    if args.trace:
        n = obs.get_tracer().save_chrome(args.trace)
        print(f"# wrote {n} spans -> {args.trace} "
              "(open at https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
