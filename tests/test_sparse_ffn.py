"""Compressed sparse-FFN inference: technique-in-the-model equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models.ffn import ffn_apply, ffn_init
from repro.models.sparse_linear import compress_ffn, sparse_ffn_apply


@pytest.fixture(scope="module")
def pruned_ffn():
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                      n_heads=4, d_ff=96, vocab=64, ffn_block_sparsity=0.4)
    # small blocks so the smoke shapes have real block structure
    params = ffn_init(jax.random.PRNGKey(0), cfg)
    # re-make the mask at 16x16 block granularity for this test
    mask = (jax.random.uniform(jax.random.PRNGKey(9), (4, 6)) > 0.4)
    params["block_mask"] = mask.astype(jnp.float32)
    return cfg, params


def test_compressed_matches_masked_dense(pruned_ffn):
    cfg, params = pruned_ffn
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64), jnp.float32)

    # reference: the training-path masked dense FFN
    import repro.models.ffn as ffn_mod
    ref = np.asarray(ffn_apply(params, cfg, x), np.float32)

    comp = compress_ffn(params, tokens=16, block=16)
    out = np.asarray(sparse_ffn_apply(comp, x), np.float32)
    err = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-6)
    assert err < 2e-2, err
    assert comp.dataflow_in in ("ip_m", "op_m", "gust_m",
                                "ip_n", "op_n", "gust_n")


def test_compression_respects_sparsity(pruned_ffn):
    cfg, params = pruned_ffn
    comp = compress_ffn(params, tokens=16, block=16)
    mask = np.asarray(params["block_mask"]) > 0
    # number of stored blocks == occupancy of the mask
    assert comp.w_gate.nnzb == int(mask.sum())
    assert comp.w_down.nnzb == int(mask.T.sum())


def test_plans_built_once_per_token_shape(pruned_ffn):
    """Phase 1 runs once per token count; repeat applies are cache hits."""
    cfg, params = pruned_ffn
    comp = compress_ffn(params, tokens=16, block=16)
    assert comp.plan_builds == 1
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 64), jnp.float32)
    for _ in range(3):
        sparse_ffn_apply(comp, x)
    assert comp.plan_builds == 1 and comp.plan_hits == 3
    # a new shape plans once at admission, then hits
    x2 = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 64), jnp.float32)
    sparse_ffn_apply(comp, x2)
    sparse_ffn_apply(comp, x2)
    assert comp.plan_builds == 2 and comp.plan_hits == 4


def test_block_pairs_are_kept_blocks_times_m_blocks_for_ip_m(pruned_ffn):
    """The program's own per-call work count: for IP, every kept weight
    block meets each of the activations' m-blocks once.  Phase 1 records
    it, with the kernel's grid steps (chunks of block pairs), its runs and
    whether it holds the activation blocks in VMEM, on ``plan.prepare``;
    the grid steps of the three matmuls also go to ``ffn.grid_steps``."""
    from repro.kernels.stream import run_chunk
    from repro import obs
    from repro.obs import trace as trace_mod

    cfg, params = pruned_ffn
    tokens = 48                                   # three 16-row m-blocks
    tracer = obs.get_tracer()
    tracer.clear()
    obs.enable()
    try:
        comp = compress_ffn(params, tokens=tokens, block=16,
                            backend="pallas", policy="ip_m")
        prepare = [s.attrs for s in tracer.spans()
                   if s.name == "plan.prepare"]
    finally:
        trace_mod._reset_override()
        tracer.clear()
    entry = comp.specialize(tokens)
    assert (entry.plan_in.dataflow, entry.plan_out.dataflow) == ("ip_m",
                                                                 "ip_m")
    kept = int((np.asarray(params["block_mask"]) > 0).sum())
    pairs = entry.block_pairs()
    assert pairs == {"gate": 3 * kept, "up": 3 * kept, "down": 3 * kept}
    assert obs.get_registry().value("ffn.block_pairs") == 9 * kept
    assert [a["block_pairs"] for a in prepare] == [3 * kept, 3 * kept]
    steps = []
    for a, plan in zip(prepare, (entry.plan_in, entry.plan_out)):
        sched = plan.aux["stream_schedule"]
        w = sched.n_work
        steps.append(-(-w // run_chunk(w)))
        assert steps[-1] < w
        assert (a["grid_steps"], a["runs"], a["a_resident"]) == (
            steps[-1], sched.n_runs, 1)
    assert entry.grid_steps() == {"gate": steps[0], "up": steps[0],
                                  "down": steps[1]}
    assert obs.get_registry().value("ffn.grid_steps") == \
        2 * steps[0] + steps[1]


def test_block_pairs_are_none_without_a_stream_schedule(pruned_ffn):
    cfg, params = pruned_ffn
    comp = compress_ffn(params, tokens=16, block=16, backend="reference")
    entry = comp.specialize(16)
    assert entry.block_pairs() == {"gate": None, "up": None, "down": None}
    assert entry.grid_steps() == {"gate": None, "up": None, "down": None}


def test_compress_span_tree_and_untraced_counts(pruned_ffn):
    """``ffn.compress`` roots the set-up spans: the masking, both plans'
    phase 1 and one pack per weight.  With tracing off the spans are the
    shared no-op, and the set-up histograms still count."""
    from repro import obs
    from repro.obs import trace as trace_mod

    cfg, params = pruned_ffn
    tracer = obs.get_tracer()
    reg = obs.get_registry()
    packs0 = reg.value("ffn.pack_s")
    obs.disable()
    try:
        assert obs.span("ffn.compress") is trace_mod._NOOP
        tracer.clear()
        compress_ffn(params, tokens=16, block=16)
        assert len(tracer) == 0
        assert reg.value("ffn.pack_s") == packs0 + 3
        obs.enable()
        compress_ffn(params, tokens=16, block=16)
        spans = tracer.spans()
    finally:
        trace_mod._reset_override()
        tracer.clear()
    by_sid = {s.sid: s for s in spans}
    (root,) = [s for s in spans if s.parent is None]
    assert root.name == "ffn.compress"
    children = [s for s in spans if s.parent == root.sid]
    assert [s.name for s in children] == ["ffn.mask", "plan.phase1",
                                          "plan.phase1", "ffn.pack",
                                          "ffn.pack", "ffn.pack"]
    assert [s.attrs["which"] for s in children[3:]] == ["gate", "up",
                                                        "down"]
    prepare = [s for s in spans if s.name == "plan.prepare"]
    assert len(prepare) == 2
    for s in prepare:
        assert by_sid[s.parent].name == "plan.phase1"
    assert reg.value("ffn.pack_s") == packs0 + 6
