"""Work counts and the peaks table of the chip benchmark."""
import json

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)
from bench import inputs, work


def test_swiglu_work_counts_a_small_mask_by_hand():
    # d=256, f=512, 128-blocks: a 2x4 mask with 3 kept blocks
    mask = np.array([[1, 0, 0, 1], [0, 1, 0, 0]])
    kept = int(mask.sum()) * 128 * 128            # 49152 weights a matrix
    w = work.swiglu_ffn_work(tokens=16, d_model=256,
                             kept_elems_per_matrix=kept, weight_itemsize=4,
                             act_itemsize=4, out_itemsize=2)
    assert w.flops == 3 * 2 * 16 * 49152 == 4718592
    assert w.bytes == 3 * 49152 * 4 + 16 * 256 * 4 + 16 * 256 * 2 == 614400


def test_least_time_names_its_bound():
    w = work.Work(flops=2e9, bytes=1e6)
    t, bound = w.least_time_s(1e12, 1e9)
    assert (t, bound) == (2e-3, "compute")
    t, bound = work.Work(flops=1.0, bytes=1e9).least_time_s(1e12, 1e9)
    assert (t, bound) == (1.0, "memory")


def test_peaks_lookup_refuses_an_unknown_device_kind():
    v5e = work.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks_for("cpu")


def test_peaks_table_names_its_source():
    table = json.loads(work.PEAKS_FILE.read_text())
    assert "Google Cloud" in table["source"]


@pytest.mark.parametrize("cfg_name", ["mixtral-8x7b-expert-ffn",
                                      "chameleon-34b-ffn"])
def test_every_seed_keeps_the_same_number_of_blocks(cfg_name):
    cfg = json.loads((bench_tiny.ROOT / "bench" / "configs" /
                      f"{cfg_name}.json").read_text())
    b = cfg["block"]
    total = (cfg["hidden_size"] // b) * (cfg["intermediate_size"] // b)
    assert inputs.kept_blocks(cfg) == round(0.25 * total)
    small = dict(cfg, hidden_size=256, intermediate_size=512)
    counts = {int(np.asarray(inputs.make_weights(small, s)[0]).sum())
              for s in (0, 7, 2**40 + 3)}
    assert counts == {inputs.kept_blocks(small)}


def test_seed_fixes_the_inputs_and_takes_wide_integers():
    cfg = {"hidden_size": 256, "intermediate_size": 512, "block": 128,
           "ffn_block_sparsity": 0.75, "dtype": "float32"}
    a = inputs.make_weights(cfg, 2**40 + 11)
    b = inputs.make_weights(cfg, 2**40 + 11)
    c = inputs.make_weights(cfg, 2**40 + 12)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    traffic = {"pool": 64, "batch": 2, "seq": 1}
    assert inputs.sample_slots(traffic, 5, 8) == \
        inputs.sample_slots(traffic, 5, 8)
    assert len(set(inputs.sample_slots(traffic, 5, 8))) == 8
