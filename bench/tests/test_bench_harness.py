"""The harness finds every cell's files by name and refuses to report
without a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

import bench_tiny
from bench import run

ROOT = bench_tiny.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files_by_name(cell):
    found = run.load_cell(cell)
    assert found["cell"]["name"] == cell
    cfg = found["config"]
    assert run.system(cfg["system"]).build
    for key in ("batch", "seq", "pool", "warmup_calls"):
        assert found["traffic"][key] > 0
    assert found["limits"]["out_gap"] > 0
    names = [m["name"] for m in found["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert found["per_layer"]
    for m in found["end_to_end"] + found["per_layer"]:
        assert callable(run.reader(m["name"]))


def test_benchmark_json_keeps_to_its_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in SPEC["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert cfg[k] != cfg["published"][k]
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["layer"], []).append(m["name"])
    for name in CELLS + [m["name"] for m in SPEC["per_layer"]]:
        assert NAME.match(name), name


def test_a_new_cell_is_found_from_new_files_alone(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    found = run.load_cell(bench_tiny.CELL, root)
    assert found["config"]["hidden_size"] == 256
    assert found["traffic"]["batch"] == 8
    with pytest.raises(run.Refused, match="no workload"):
        run.load_cell("no-such-cell", root)


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "2147483659", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_run_refuses_to_report_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_the_run_refuses_in_a_directory_of_benchmark_files_alone(tmp_path):
    bench_tiny.copy_benchmark_only(tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_a_compile_inside_the_window_is_counted():
    import jax
    import jax.numpy as jnp

    with run._counting_compiles() as count:
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
    assert count[0] > 0
    with run._counting_compiles() as quiet:
        pass
    assert quiet[0] == 0
