#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip, in one process.

    python3 bench/control.py --workload <name> --seeds 1 2 ... [--control 3]
                             [--seconds 2]

For each seed it runs the cell as ``run.py`` does (set-up, a short window at
the cell's own load, the comparison of the sampled in-window answers), and
prints the readings as one JSON line: ``out_gap``, the program's widest gap
to the reference.  For the first ``--control`` seeds it also reads the
control, the reference computed with every array in bfloat16 and put in
the program's place at the same inputs (``control_gap``), and both against
a float32 ``HIGHEST`` reference.  The last line sums up: the lower reading
(the largest ``out_gap``) and the upper (the smallest ``control_gap``).
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path.pop(0)
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH.parent)]

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)["cell"]
    run.use_compile_cache()
    devices = run.tpu_devices(int(cell["chips"]))
    outs, ctl = [], []
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        out = run.run_cell(args.workload, seed, args.seconds, False, devices,
                           t_start=t0, control=i < args.control)
        info = out["info"]
        row = {"seed": seed, "correct": out["correct"],
               "calls": out["attempted"], **{
                   k: info[k] for k in ("out_gap", "gaps", "control_gap",
                                        "out_gap_f32", "control_gap_f32")
                   if k in info},
               "setup_s": out["metrics"].get("setup_s", {}).get("value")}
        print(json.dumps(row), flush=True)
        outs.append(info["out_gap"])
        if "control_gap" in info:
            ctl.append(info["control_gap"])
    print(json.dumps({"workload": args.workload, "seeds": len(outs),
                      "lower": max(outs), "upper": min(ctl) if ctl else None,
                      "control_seeds": len(ctl)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
