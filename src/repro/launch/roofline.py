import os

from ..config import virtual_devices

virtual_devices(512)

"""Roofline analysis (deliverable g).

For each (arch × shape) on the single-pod mesh, derive the three roofline
terms from compiled artifacts:

    compute    = HLO_FLOPs_per_chip / peak_FLOP/s        (197 TF/s bf16)
    memory     = HLO_bytes_per_chip / HBM_bw             (819 GB/s)
    collective = collective_bytes_per_chip / link_bw     (50 GB/s ICI)

Method.  XLA's ``cost_analysis`` counts ``while``-loop bodies once, so the
production lowering (scanned layers) undercounts.  We therefore lower **cost
probes**: reduced-depth model variants (1 and 2 layer-periods) with every
scan unrolled (loop-free HLO → exact counts) and extrapolate linearly over
the layer count:

    total = probe1 + (n_periods - 1) × (probe2 - probe1)

— exact, because layers are identical.  Memory comes from the full dry-run
artifact (launch/dryrun.py).  MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference)
with N = active params (MoE counts top_k/E of expert params); the ratio
MODEL_FLOPS / HLO_FLOPs measures how much compiled compute is useful.

Usage:
    PYTHONPATH=src python -m repro.launch.roofline --arch X --shape Y
    PYTHONPATH=src python -m repro.launch.roofline --all
    PYTHONPATH=src python -m repro.launch.roofline --summary   # markdown
"""
import argparse       # noqa: E402
import dataclasses    # noqa: E402
import json           # noqa: E402
import sys            # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402

import jax            # noqa: E402
import numpy as np    # noqa: E402

from .dryrun import collective_stats          # noqa: E402
from .mesh import make_production_mesh        # noqa: E402
from .specs import TRAIN_MICROBATCHES, cell_is_supported  # noqa: E402
from ..configs import ARCH_IDS, get_config    # noqa: E402
from ..configs.base import SHAPES, TrainConfig  # noqa: E402

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
LINK_BW = 50e9             # bytes/s / link


# ---------------------------------------------------------------------------
# analytic MODEL_FLOPS
# ---------------------------------------------------------------------------


def active_params(cfg) -> float:
    """Active parameter count (MoE experts weighted by top_k / E)."""
    from ..models import build_model
    model = build_model(cfg)
    struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    total = 0.0
    def visit(path, leaf):
        nonlocal total
        p = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
        n = float(np.prod(leaf.shape))
        if cfg.moe is not None and any(
                w in p for w in ("w_gate", "w_up", "w_down")) \
                and len(leaf.shape) >= 3:
            n *= cfg.moe.top_k / cfg.moe.num_experts
        total += n
    jax.tree_util.tree_map_with_path(visit, struct)
    return total


def model_flops(cfg, shape) -> float:
    n = active_params(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    if cfg.kind == "encdec":
        # encoder sees seq/4 frame tokens, decoder sees the text tokens
        # (1 for prefill's priming token); N splits ~evenly enc/dec
        enc_tokens = shape.global_batch * max(1, shape.seq_len // 4)
        if shape.kind == "train":
            return 6.0 * (n / 2) * enc_tokens + 6.0 * (n / 2) * tokens
        if shape.kind == "prefill":
            return 2.0 * (n / 2) * enc_tokens + 2.0 * (n / 2) * shape.global_batch
        return 2.0 * (n / 2) * tokens          # decode: decoder only
    if shape.kind == "train":
        return 6.0 * n * tokens
    return 2.0 * n * tokens


# ---------------------------------------------------------------------------
# cost probes
# ---------------------------------------------------------------------------


def _probe_cfg(cfg, mult: int):
    period = len(cfg.segments()[0][0])
    if cfg.kind == "encdec":
        return dataclasses.replace(cfg, n_layers=2 * mult,
                                   n_encoder_layers=mult)
    return dataclasses.replace(cfg, n_layers=period * mult)


def _n_periods(cfg) -> int:
    if cfg.kind == "encdec":
        return cfg.n_encoder_layers          # enc and dec scale together
    period = len(cfg.segments()[0][0])
    return cfg.n_layers // period


def _probe_cost(cfg, shape, mesh, tcfg_over=None) -> dict:
    """Lower one unrolled probe and return {'flops','bytes',collectives}."""
    from ..models import build_model
    from ..models import scan_config
    from ..sharding import batch_sharding, cache_sharding, params_sharding
    from ..train import init_train_state, make_train_step
    import jax.numpy as jnp

    model = build_model(cfg)
    with scan_config.unrolled():
        if shape.kind == "train":
            tcfg = TrainConfig(global_batch=shape.global_batch,
                               seq_len=shape.seq_len, microbatches=1,
                               **(tcfg_over or {}))
            state = jax.eval_shape(
                lambda k: init_train_state(model, k, tcfg),
                jax.random.PRNGKey(0))
            tok = jax.ShapeDtypeStruct(
                (shape.global_batch, shape.seq_len), jnp.int32)
            batch = {"tokens": tok, "targets": tok}
            if cfg.frontend == "frames":
                batch["frames"] = jax.ShapeDtypeStruct(
                    (shape.global_batch, shape.seq_len // 4, cfg.d_model),
                    jnp.bfloat16)
            fn = make_train_step(model, tcfg)
            args = (state, batch)
            shardings = (
                type(state)(params=params_sharding(state.params, mesh, cfg),
                            opt=type(state.opt)(
                                step=batch_sharding(state.opt.step, mesh),
                                m=params_sharding(state.opt.m, mesh, cfg),
                                v=params_sharding(state.opt.v, mesh, cfg)),
                            ef=None),
                batch_sharding(batch, mesh))
        else:
            params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            cache = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            if shape.kind == "prefill":
                if cfg.kind == "encdec":
                    inputs = {"frames": jax.ShapeDtypeStruct(
                        (shape.global_batch, shape.seq_len // 4, cfg.d_model),
                        jnp.bfloat16),
                        "tokens": jax.ShapeDtypeStruct(
                            (shape.global_batch, 1), jnp.int32)}
                else:
                    inputs = jax.ShapeDtypeStruct(
                        (shape.global_batch, shape.seq_len), jnp.int32)
                fn = lambda p, i, c: model.prefill(p, i, c)
                args = (params, inputs, cache)
                shardings = (params_sharding(params, mesh, cfg),
                             batch_sharding(inputs, mesh),
                             cache_sharding(cache, mesh, cfg))
            else:
                tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
                fn = lambda p, c, t: model.decode_step(p, c, t)
                args = (params, cache, tok)
                shardings = (params_sharding(params, mesh, cfg),
                             cache_sharding(cache, mesh, cfg),
                             batch_sharding(tok, mesh))
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=shardings).lower(*args)
            compiled = lowered.compile()
    cost = compiled.cost_analysis()
    colls = collective_stats(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": float(sum(v["bytes"] for v in colls.values())),
        "collectives": colls,
    }


from .specs import VARIANTS            # noqa: E402  (hillclimb variants)


def analyze_cell(arch: str, shape_name: str, *,
                 dryrun_dir: str = "artifacts/dryrun",
                 variant: str = "baseline") -> dict:
    reason = cell_is_supported(arch, shape_name)
    if reason:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": reason}
    cfg_fn, tcfg_over = VARIANTS[variant]
    cfg = cfg_fn(get_config(arch))
    shape = SHAPES[shape_name]
    mesh = make_production_mesh()
    t0 = time.time()

    p1 = _probe_cost(_probe_cfg(cfg, 1), shape, mesh, tcfg_over)
    p2 = _probe_cost(_probe_cfg(cfg, 2), shape, mesh, tcfg_over)
    reps = _n_periods(cfg)

    # GSPMD may pick different partitions at different depths; floor the
    # marginal at 0 (p2 < p1 flags an unreliable per-device extrapolation)
    reliable = p2["flops"] >= p1["flops"]

    def extrap(key):
        return p1[key] + (reps - 1) * max(0.0, p2[key] - p1[key])

    flops = extrap("flops")
    hbytes = extrap("bytes")
    cbytes = extrap("collective_bytes")
    mb = TRAIN_MICROBATCHES.get(arch, 8) if shape.kind == "train" else 1
    # probes run microbatches=1 at the full global batch; flops/bytes are the
    # whole step's, so no mb scaling is needed (mb only re-chunks them)

    compute_s = flops / PEAK_FLOPS
    memory_s = hbytes / HBM_BW
    collective_s = cbytes / LINK_BW
    dominant = max(("compute", compute_s), ("memory", memory_s),
                   ("collective", collective_s), key=lambda kv: kv[1])[0]

    mf = model_flops(cfg, shape)
    chips = 256
    useful_ratio = mf / max(1.0, flops * chips)
    if not reliable or useful_ratio > 1.5:
        reliable = False
        useful_ratio = min(useful_ratio, 1.0)

    # memory from the full dry-run artifact, when present
    mem = None
    path = os.path.join(dryrun_dir, f"{arch}__{shape_name}__sp.json")
    if os.path.exists(path):
        with open(path) as f:
            d = json.load(f)
        mem = d.get("memory")

    hint = {
        "compute": "raise MXU utilization (fusion, larger per-chip tiles, "
                   "less recompute)",
        "memory": "cut HBM traffic (better remat policy, fuse elementwise "
                  "chains, bf16 psums where safe)",
        "collective": "re-shard to shrink per-layer all-gathers "
                      "(larger TP blocks / fewer FSDP gathers) and overlap "
                      "collectives with compute",
    }[dominant]

    return {
        "arch": arch, "shape": shape_name, "status": "ok",
        "variant": variant,
        "seconds": round(time.time() - t0, 1),
        "per_chip": {"flops": flops, "hbm_bytes": hbytes,
                     "collective_bytes": cbytes},
        "terms_s": {"compute": compute_s, "memory": memory_s,
                    "collective": collective_s},
        "dominant": dominant,
        "model_flops": mf,
        "useful_flops_ratio": useful_ratio,
        "ratio_reliable": reliable,
        "memory": mem,
        "microbatches": mb,
        "hint": hint,
        "probe": {"p1": p1, "p2": p2, "periods": reps},
    }


def summary(roofline_dir: str = "artifacts/roofline") -> str:
    rows = []
    for name in sorted(os.listdir(roofline_dir)):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(roofline_dir, name)) as f:
            rows.append(json.load(f))
    lines = [
        "| arch | shape | compute_s | memory_s | collective_s | dominant "
        "| MODEL_FLOPS | useful | mem/dev GiB |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r.get("status") == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                         f"skipped: {r['reason'][:40]}… | — | — | — |")
            continue
        if r.get("status") != "ok":
            continue
        t = r["terms_s"]
        mem = r.get("memory") or {}
        peak = mem.get("peak_bytes_per_device", 0) / 2 ** 30
        lines.append(
            f"| {r['arch']} | {r['shape']} | {t['compute']:.3e} | "
            f"{t['memory']:.3e} | {t['collective']:.3e} | {r['dominant']} | "
            f"{r['model_flops']:.2e} | {100 * r['useful_flops_ratio']:.0f}% | "
            f"{peak:.1f} |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--summary", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    ap.add_argument("--out", default="artifacts/roofline")
    args = ap.parse_args()

    if args.summary:
        print(summary(args.out))
        return

    cells = []
    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    for a in archs:
        for s in shapes:
            cells.append((a, s))
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    suffix = "" if args.variant == "baseline" else f"__{args.variant}"
    for arch, shape in cells:
        path = os.path.join(args.out, f"{arch}__{shape}{suffix}.json")
        try:
            res = analyze_cell(arch, shape, variant=args.variant)
            if res["status"] == "ok":
                t = res["terms_s"]
                print(f"[roofline] {arch} × {shape}: "
                      f"compute={t['compute']:.3e}s memory={t['memory']:.3e}s "
                      f"coll={t['collective']:.3e}s -> {res['dominant']} "
                      f"useful={100*res['useful_flops_ratio']:.0f}% "
                      f"({res['seconds']}s)")
            else:
                print(f"[roofline] {arch} × {shape}: {res['status']}")
        except Exception as e:   # noqa: BLE001
            failures += 1
            traceback.print_exc()
            res = {"arch": arch, "shape": shape, "status": "error",
                   "error": repr(e)}
        with open(path, "w") as f:
            json.dump(res, f, indent=2)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
