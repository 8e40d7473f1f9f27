"""The pruned MoE FFN system against its plain reference, and its check,
at a small size on the CPU (interpret-mode kernels): hidden 256, experts of
width 256, 32 experts in 8 groups of which this holder keeps group 0,
top-8, a shared expert; one dense layer then two MoE layers."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import inputs, inputs_moe, reference_moe
from bench.systems import pruned_moe_ffn as moe
from repro.models.sparse_linear import compress_ffn
from repro.models.sparse_moe import compress_experts, moe_block_apply

SEED = 2**33 + 7
CFG_FILE = bench_tiny.ROOT / "bench" / "configs" / "deepseek-v3-moe-ffn.json"
CELL = "dsv3-moe.decode1024-skew"


def tiny_cfg(**over):
    """The cell's configuration with only its sizes changed."""
    cfg = json.loads(CFG_FILE.read_text())
    cfg.update(hidden_size=256, intermediate_size=512,
               moe_intermediate_size=256, n_routed_experts=4,
               published={"num_hidden_layers": 61, "n_routed_experts": 32},
               moe_layers=2, block=64, reference_rows=64)
    cfg.update(over)
    return cfg


TRAFFIC = {"batch": 96, "seq": 1, "pool": 3, "warmup_calls": 1,
           "topics": 4, "zipf": 1.1, "noise": 0.5}


@pytest.fixture(scope="module")
def run():
    cfg = tiny_cfg()
    layer = moe.build(cfg, TRAFFIC, SEED, jax.devices()[:1])
    answers = {s: layer.call(layer.pool[s]).block_until_ready()
               for s in (0, 2)}
    xs = {s: layer.pool[s] for s in answers}
    info = dict(layer.info)
    layer.release()
    return cfg, answers, xs, info


def test_the_program_matches_the_reference(run):
    cfg, answers, xs, _ = run
    limit = json.loads((bench_tiny.ROOT / "bench" / "limits" /
                        f"{CELL}.json").read_text())["out_gap"]
    r = moe.check(cfg, SEED, "cpu", answers, xs)
    assert r["routes_ok"] and r["route_mismatch"] == 0
    assert r["route_token_layers"] == 2 * 2 * TRAFFIC["batch"]
    assert r["out_gap"] <= 1e-5 < limit
    assert len(r["gaps"]) == 2


def test_executed_slots_are_the_live_rows_in_whole_slots(run):
    cfg, answers, _, info = run
    held = inputs_moe.held(cfg)
    for a in answers.values():
        ids, counts = np.asarray(a.ids), np.asarray(a.counts)
        for layer_ids, (rows, slots) in zip(ids, counts):
            mine = layer_ids[(layer_ids >= held.start) &
                             (layer_ids < held.stop)] - held.start
            np.testing.assert_array_equal(
                rows, np.bincount(mine, minlength=len(held)))
            np.testing.assert_array_equal(slots, -(-rows // cfg["block"]))
    assert info["held_experts"] == len(held)


def test_a_route_that_is_no_near_tie_is_refused(run):
    cfg, answers, xs, _ = run
    s = min(answers)
    ids = np.array(answers[s].ids)
    row = ids[0, 0]
    row[-1] = next(e for e in range(32) if e not in row)  # a far-off expert
    bad = dict(answers)
    bad[s] = moe.Answer(answers[s].out, jnp.asarray(ids), answers[s].counts)
    r = moe.check(cfg, SEED, "cpu", bad, xs)
    assert r["route_mismatch"] == 1 and r["route_gap_max"] > 1e-3
    assert not r["routes_ok"] and r["out_gap"] == float("inf")
    # a mismatch inside route_eps passes as a near-tie
    r = moe.check(cfg, SEED, "cpu", bad, xs,
                  limits={"route_eps": 1.0, "route_mismatch_share": 0.01})
    assert r["routes_ok"] and r["out_gap"] < float("inf")


def test_the_bfloat16_control_is_refused(run):
    cfg, answers, xs, _ = run
    limits = moe.route_limits(cfg)
    r = moe.check(cfg, SEED, "cpu", answers, xs, control=True)
    assert r["control_gap"] > json.loads(
        (bench_tiny.ROOT / "bench" / "limits" / f"{CELL}.json").read_text()
    )["out_gap"]
    assert not r["control_routes_ok"]
    assert r["control_route_mismatch_share"] > limits["route_mismatch_share"]


def test_expert_masks_are_the_weights_masks():
    cfg = tiny_cfg(held_group=3)
    masks = inputs_moe.expert_masks(cfg, SEED, 2)
    for i, e in enumerate(inputs_moe.held(cfg)):
        np.testing.assert_array_equal(
            masks[i], np.asarray(inputs_moe.expert_ffn(cfg, SEED, 2, e)[0]) > 0)
    kept = inputs.kept_blocks(dict(cfg, intermediate_size=256))
    assert (masks.sum((1, 2)) == kept).all()


def test_eight_held_groups_add_up_to_the_uncut_layer():
    """Each of the 8 holders (one routing group each) computes its share;
    the shares less 7 copies of what every holder computes alike (the
    input and the shared expert) are the uncut reference layer."""
    layer = 1
    cfg = tiny_cfg(dense_layers=0, moe_layers=1)
    x = jax.random.normal(jax.random.key(4), (48, cfg["hidden_size"]))
    parts = inputs_moe.shared_ffn(cfg, SEED, layer)
    params = {"w_gate": {"w": parts[1]}, "w_up": {"w": parts[2]},
              "w_down": {"w": parts[3]}, "block_mask": parts[0]}
    shared = compress_ffn(params, tokens=x.shape[0], backend="pallas",
                          block=cfg["block"], verify=False)
    w_r, bias = inputs_moe.router(cfg, SEED, layer)
    spec, ones = moe.router_spec(cfg), inputs_moe.norm(cfg)
    total = jnp.zeros_like(x)
    for g in range(8):
        cfg_g = dict(cfg, held_group=g)
        experts = compress_experts(
            inputs_moe.expert_masks(cfg_g, SEED, layer),
            lambda lo, hi: inputs_moe.experts(cfg_g, SEED, layer, lo, hi),
            block=cfg["block"])
        out, _, _ = moe_block_apply(x[None], ones, w_r, bias, experts,
                                    shared, spec, eps=cfg["rms_norm_eps"],
                                    held_lo=inputs_moe.held(cfg_g).start)
        total = total + out[0]
    alike, _ = reference_moe.forward(dict(cfg, n_routed_experts=0), SEED, x)
    uncut, _ = reference_moe.forward(dict(cfg, n_routed_experts=32), SEED, x)
    np.testing.assert_allclose(np.asarray(total - 7 * alike),
                               np.asarray(uncut), rtol=1e-5, atol=1e-5)


def test_a_group_level_near_tie_reads_as_one():
    """Two groups tie for the last place to 1e-6, and the program took the
    other one: its experts in that group are the reference's own choice
    there, so only the group gap is read, and it is the tie's width."""
    z = np.full((1, 32), 0.1, np.float32)
    z[0, [0, 1, 4, 5, 8, 9]] = 0.9              # groups 0-2: clear winners
    z[0, [12, 13]] = 0.6                        # group 3: top two sum 1.2
    z[0, [16, 17]] = [0.65, 0.55 - 1e-6]        # group 4: 1e-6 below it
    kw = dict(n_group=8, topk_group=4, top_k=8)
    own = np.asarray(reference_moe.select(jnp.asarray(z), **kw)[0])
    assert {12, 13} <= set(own[0])
    ids = np.array([[0, 1, 4, 5, 8, 9, 16, 17]], np.int32)
    differ, group_gap, expert_gap = (np.asarray(a) for a in
                                     reference_moe.tie_gaps(z, ids, **kw))
    assert differ[0] and expert_gap[0] == 0.0
    assert 0 < group_gap[0] < 2e-6
    # a program that drew from five groups is never a near-tie
    spread = np.array([[0, 1, 4, 5, 8, 12, 16, 20]], np.int32)
    assert np.isinf(np.asarray(reference_moe.tie_gaps(z, spread, **kw)[2]))
