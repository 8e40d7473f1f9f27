"""A tiny pruned-FFN cell written into a scratch root, for CPU tests of
the harness (interpret-mode kernels, a fraction of a second per run)."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "tiny-ffn.decode8"


def make_root(tmp: Path, limit: float = 1e-4) -> Path:
    """A checkout-like root holding one tiny cell's BENCHMARK.json and files.

    The configuration copies the Mixtral one and changes only its sizes."""
    cfg = json.loads((ROOT / "bench" / "configs" /
                      "mixtral-8x7b-expert-ffn.json").read_text())
    cfg.update(name="tiny-ffn", hidden_size=256, intermediate_size=512,
               reference_rows=16)
    traffic = json.loads((ROOT / "bench" / "traffic" /
                          "decode16.json").read_text())
    traffic.update(batch=8, pool=4, warmup_calls=1)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny-ffn", "source": "test",
                        "file": "bench/configs/tiny-ffn.json",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = [{"name": CELL, "config": "tiny-ffn",
                          "traffic": "tiny8", "chips": 1, "why": "test"}]
    for m in spec["per_layer"]:
        m["workloads"] = [CELL]
    bench = tmp / "bench"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    (bench / "configs" / "tiny-ffn.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny8.json").write_text(json.dumps(traffic))
    (bench / "limits" / f"{CELL}.json").write_text(
        json.dumps({"out_gap": limit}))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def copy_benchmark_only(dst: Path) -> Path:
    """BENCHMARK.json and the files under ``bench/`` and nothing else."""
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst
