"""The correctness check fails its control and every fault the cells can
have, and passes the sound program (tiny cell, CPU, interpret mode)."""
import json
import time

import jax
import jax.numpy as jnp
import pytest

import bench_tiny
from bench import inputs, reference, run
from bench.systems import pruned_swiglu_ffn as ffn

LIMITS = {p.stem: json.loads(p.read_text())["out_gap"]
          for p in (bench_tiny.ROOT / "bench" / "limits").glob("*.json")}


def _run(tmp_path, monkeypatch, broken=None):
    if broken is not None:
        real = ffn.sparse_ffn_apply
        monkeypatch.setattr(ffn, "sparse_ffn_apply",
                            lambda comp, x: broken(real, comp, x))
    root = bench_tiny.make_root(tmp_path, limit=min(LIMITS.values()))
    return run.run_cell(bench_tiny.CELL, 2**33 + 5, 0.3, False,
                        jax.devices()[:1], root=root,
                        t_start=time.perf_counter())


def test_the_sound_program_is_correct(tmp_path, monkeypatch):
    out = _run(tmp_path, monkeypatch)
    assert out["correct"] and out["failed"] == 0
    assert out["info"]["compiles_in_window"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


def _answer_altered(real, comp, x):
    y = real(comp, x)
    return y.at[0, 0, 0].add(1e-2 * jnp.max(jnp.abs(y)))


def _half_left_out(real, comp, x):
    y = real(comp, x)
    half = y.shape[0] // 2
    return y.at[half:].set(jnp.mean(y[:half], axis=0))


def _control_in_place(real, comp, x):
    entry = comp.specialize(x.shape[0] * x.shape[1])
    w = [op.todense() for op in (entry.w_gate, entry.w_up, entry.w_down)]
    y = reference.swiglu_lowp(x.reshape(-1, x.shape[-1]), *w)
    return y.reshape(x.shape)


@pytest.mark.parametrize("broken", [_answer_altered, _half_left_out,
                                    _control_in_place])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, broken):
    out = _run(tmp_path, monkeypatch, broken)
    assert not out["correct"]
    assert out["failed"] > 0
    assert out["checks"]["out_gap"]["value"] > out["checks"]["out_gap"][
        "limit"]


@pytest.mark.parametrize("cell", sorted(LIMITS))
def test_the_control_reads_above_each_cells_limit(cell):
    cfg = {"hidden_size": 256, "intermediate_size": 512, "block": 128,
           "ffn_block_sparsity": 0.75, "dtype": "float32"}
    w = reference.masked_weights(*inputs.make_weights(cfg, 17), block=128)
    x = jax.random.normal(jax.random.key(17), (16, 256))
    ref = reference.swiglu(x, *w)
    assert reference.widest_gap(reference.swiglu_lowp(x, *w), ref) > \
        LIMITS[cell]
