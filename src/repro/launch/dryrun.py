import os

from ..config import virtual_devices

virtual_devices(512, override=True)

"""Multi-pod dry-run: lower + compile every (architecture × input shape) for
the production meshes, and record memory / cost / collective analyses.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
        --shape train_4k [--multipod] [--out artifacts/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod]

The virtual_devices call above MUST execute before jax's first backend init
(device count locks then, not at import); do not move it below the jax
import.
"""
import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import numpy as np   # noqa: E402

from .mesh import make_production_mesh                      # noqa: E402
from .specs import build_cell, cell_is_supported, SKIPS     # noqa: E402
from ..configs import ARCH_IDS                              # noqa: E402
from ..configs.base import SHAPES                           # noqa: E402

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape like ``bf16[4,1024,128]`` (or a tuple)."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str):
    """Sum result-shape bytes of every collective op (per-device payload
    upper bound) and count ops, per collective kind."""
    stats = {c: {"count": 0, "bytes": 0} for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # e.g.:  %ag = bf16[8,128]{1,0} all-gather(%x), replica_groups=...
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],{}\s]+?)\s+"
                     r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)", s)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        stats[kind]["count"] += 1
        stats[kind]["bytes"] += _shape_bytes(shape_str)
    return stats


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             microbatches=None, verbose: bool = True,
             variant: str = "baseline"):
    reason = cell_is_supported(arch, shape_name)
    if reason:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh, microbatches=microbatches,
                      variant=variant)
    donate = cell.static_desc.get("donate", ())
    with jax.set_mesh(mesh):
        lowered = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                          donate_argnums=donate).lower(*cell.args)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = collective_stats(hlo)
    result = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "status": "ok",
        "kind": cell.static_desc["kind"],
        "seconds": round(time.time() - t0, 1),
        "devices": int(np.prod(mesh.devices.shape)),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_per_device": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes,
        },
        "cost": {
            "flops": cost.get("flops", 0.0),
            "transcendentals": cost.get("transcendentals", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0),
        },
        "collectives": colls,
        "collective_bytes_total": sum(v["bytes"] for v in colls.values()),
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} "
              f"({'2x16x16' if multi_pod else '16x16'}): OK "
              f"flops={result['cost']['flops']:.3e} "
              f"mem/dev={result['memory']['peak_bytes_per_device']/2**30:.2f}GiB "
              f"coll={result['collective_bytes_total']/2**20:.1f}MiB "
              f"({result['seconds']}s)")
        print("  memory_analysis:", mem)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        archs = [args.arch] if args.arch else ARCH_IDS
        shapes = [args.shape] if args.shape else list(SHAPES)
        for arch in archs:
            for shape in shapes:
                cells.append((arch, shape))

    meshes = [False, True] if args.both_meshes else [args.multipod]
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    vsuffix = "" if args.variant == "baseline" else f"__{args.variant}"
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}{vsuffix}"
            path = os.path.join(args.out, tag + ".json")
            try:
                res = run_cell(arch, shape, multi_pod=mp,
                               microbatches=args.microbatches,
                               variant=args.variant)
            except Exception as e:  # noqa: BLE001 — record and continue
                failures += 1
                traceback.print_exc()
                res = {"arch": arch, "shape": shape, "multi_pod": mp,
                       "status": "error", "error": repr(e)}
            with open(path, "w") as f:
                json.dump(res, f, indent=2)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
