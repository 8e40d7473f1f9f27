"""The sparse MoE layer at a small size on the CPU: its stacked plans are
the planned ``ip_m`` schedules, it drops no token however skewed the
routing, and one compiled program serves every routing."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import flexagon_plan
from repro.kernels.stream import row_schedules
from repro.models.sparse_linear import compress_ffn, sparse_ffn_apply
from repro.models.sparse_moe import (RouterSpec, compress_experts, n_slots,
                                     route, sparse_moe_apply)

D, F, B = 256, 256, 64          # hidden, expert width, block
SPEC = RouterSpec(n_experts=32, n_group=8, topk_group=4, top_k=8, scale=2.5)
HELD = 4                        # this holder's experts: group 0


def _masked(rng, shape, keep):
    mask = rng.random((shape[0] // B, shape[1] // B)) < keep
    mask[0, 0] = True
    full = np.repeat(np.repeat(mask, B, 0), B, 1)
    w = rng.standard_normal(shape).astype(np.float32) / np.sqrt(shape[0])
    return mask, (w * full).astype(np.float32)


@pytest.fixture(scope="module")
def layer():
    rng = np.random.default_rng(3)
    masks, wg, wu, wd = [], [], [], []
    for _ in range(HELD):
        m, g = _masked(rng, (D, F), 0.4)
        full = np.repeat(np.repeat(m, B, 0), B, 1)
        u = rng.standard_normal((D, F)).astype(np.float32) * full / 16
        dn = rng.standard_normal((F, D)).astype(np.float32) * full.T / 16
        masks.append(m)
        wg.append(g)
        wu.append(u)
        wd.append(dn)
    dense = [np.stack(w) for w in (wg, wu, wd)]
    experts = compress_experts(
        np.stack(masks), lambda lo, hi: tuple(w[lo:hi] for w in dense),
        block=B)
    sm, sg = _masked(rng, (D, F), 0.5)
    params = {"w_gate": {"w": sg}, "w_up": {"w": sg}, "w_down": {"w": sg.T},
              "block_mask": sm.astype(np.float32)}
    w_r = rng.standard_normal((D, SPEC.n_experts)).astype(np.float32) / 16
    return {"experts": experts, "dense": dense, "params": params,
            "w_r": jnp.asarray(w_r)}


def _shared(layer, tokens):
    return compress_ffn(layer["params"], tokens=tokens, backend="pallas",
                        block=B, verify=False)


def _expert_ffn(layer, e, h):
    wg, wu, wd = (w[e] for w in layer["dense"])
    hp = jax.lax.Precision.HIGHEST
    g = jax.nn.silu(jnp.dot(h, wg, precision=hp))
    return jnp.dot(g * jnp.dot(h, wu, precision=hp), wd, precision=hp)


def test_row_schedules_are_the_planned_ip_schedule_of_one_block_row(layer):
    """Each expert's stacked schedule is what ``flexagon_plan`` plans for
    one dense block row against its weight, with one run for every output
    column (a column with no kept block multiplies the zero block), and
    its packed blocks are the plan's packed weight."""
    sched = row_schedules(np.stack([np.ones((2, 3), bool),
                                    np.eye(2, 3, dtype=bool)]))
    assert sched.a_slot.shape == (2, 6) and sched.n_runs == 4
    np.testing.assert_array_equal(sched.b_slot[1, :3], [0, 1, -1])
    np.testing.assert_array_equal(sched.run_id[1], [0, 1, 2, 3, 3, 3])
    experts = layer["experts"]
    got = jax.tree.map(np.asarray, experts.sched_in)
    w_b = (experts.w_gate.shape[0] - 1) // HELD
    np.testing.assert_array_equal(np.asarray(experts.w_gate)[-1], 0.0)
    for e in range(HELD):
        w = layer["dense"][0][e]
        plan = flexagon_plan((B, D), w, dataflow="ip_m",
                             block_shape=(B, B, B), backend="pallas",
                             verify=False)
        want = plan.aux["stream_schedule"]
        runs = got.run_id[e, got.is_last[e] == 1]
        np.testing.assert_array_equal(runs[:F // B], np.arange(F // B))
        real = (got.b_slot[e] >= 0) & \
            (np.arange(got.b_slot.shape[1]) < got.real_w[e, 0])
        for field in ("a_slot", "b_slot", "cj"):
            np.testing.assert_array_equal(
                getattr(got, field)[e][real], np.asarray(getattr(want, field)),
                err_msg=field)
        n = want.n_work
        packed = np.asarray(experts.w_gate)[e * w_b:e * w_b + n]
        np.testing.assert_array_equal(packed, np.asarray(plan.pack_b(w).data))


def test_slot_bound_holds_for_the_most_skewed_routings():
    rng = np.random.default_rng(0)
    for tokens, top_k, held in ((96, 8, 4), (1024, 8, 32), (5, 2, 8)):
        bound = n_slots(tokens, top_k, held, B)
        for _ in range(200):
            k = min(top_k, held)
            rows = np.zeros(held, int)
            for t in range(tokens):
                rows[rng.choice(held, rng.integers(0, k + 1),
                                replace=False)] += 1
            assert np.sum(-(-rows // B)) <= bound
        # every token on every held expert it can pick: the bound is met
        full = np.zeros(held, int)
        full[:min(top_k, held)] = tokens
        assert np.sum(-(-full // B)) <= bound


def test_dropless_when_one_expert_gets_every_token(layer):
    """A bias that sends every token to held expert 2 and none to the other
    held experts: expert 2 runs all of them over several slots, the others
    fetch nothing, and the answer is the plain sum."""
    tokens = 200
    bias = np.zeros(SPEC.n_experts, np.float32)
    bias[:HELD] = -10.0
    bias[2] = 30.0          # group 0 always scores highest
    h = jax.random.normal(jax.random.key(1), (tokens, D))
    shared = _shared(layer, tokens)
    y, ids, counts = jax.jit(
        lambda h, b: sparse_moe_apply(h, layer["w_r"], b, layer["experts"],
                                      shared, SPEC))(h, jnp.asarray(bias))
    assert (np.asarray(ids) == 2).any(1).all()
    assert not np.isin(np.asarray(ids), [0, 1, 3]).any()
    np.testing.assert_array_equal(np.asarray(counts),
                                  [[0, 0, tokens, 0],
                                   [0, 0, -(-tokens // B), 0]])
    _, gates = route(h, layer["w_r"], jnp.asarray(bias), SPEC)
    g2 = jnp.sum(jnp.where(ids == 2, gates, 0.0), axis=1)
    want = sparse_ffn_apply(shared, h[None])[0] + \
        g2[:, None] * _expert_ffn(layer, 2, h)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_one_program_serves_every_routing_with_no_host_callback(layer):
    tokens = 64
    shared = _shared(layer, tokens)
    bias = jnp.zeros(SPEC.n_experts, jnp.float32)
    traces = []

    def step(h):
        traces.append(1)
        return sparse_moe_apply(h, layer["w_r"], bias, layer["experts"],
                                shared, SPEC)

    fn = jax.jit(step)
    h1 = jax.random.normal(jax.random.key(5), (tokens, D))
    h2 = jax.random.normal(jax.random.key(6), (tokens, D)) * 3.0
    (_, ids1, c1), (_, ids2, c2) = fn(h1), fn(h2)
    assert not np.array_equal(np.asarray(c1), np.asarray(c2))
    assert not np.array_equal(np.asarray(ids1), np.asarray(ids2))
    assert len(traces) == 1 and fn._cache_size() == 1
    text = str(jax.make_jaxpr(step)(h1))
    for prim in ("callback", "pure_callback", "io_callback"):
        assert prim not in text
    # every live row of the held experts is accounted for, none twice
    held = np.asarray(ids1) < HELD
    np.testing.assert_array_equal(np.asarray(c1)[0],
                                  np.bincount(np.asarray(ids1)[held],
                                              minlength=HELD))


def test_slot_panels_give_the_streamed_answer_bit_for_bit(layer,
                                                          monkeypatch):
    """The grouped kernels holding each live slot's A row panel in VMEM
    run the same dots in the same order as with every A block streamed:
    the layer's output is the same to the bit.  The VMEM budget is cut so
    that two panels fit but not the whole stack (panel mode), then so that
    not even two fit (streamed); the ``moe.a_panel_bytes`` gauge follows."""
    from repro import obs
    from repro.kernels import stream

    tokens = 64
    ka, blk = D // B, B * B * 4
    slots = n_slots(tokens, SPEC.top_k, HELD, B)
    shared = _shared(layer, tokens)
    bias = jnp.zeros(SPEC.n_experts, jnp.float32)
    h = jax.random.normal(jax.random.key(7), (tokens, D))

    def run(budget, mode):
        monkeypatch.setattr(stream, "A_RESIDENT_BYTES", budget)
        stream._grouped_spmm.clear_cache()
        assert stream.grouped_a_mode(slots, ka, blk) == mode
        out = jax.jit(lambda h: sparse_moe_apply(
            h, layer["w_r"], bias, layer["experts"], shared, SPEC))(h)
        return out, obs.get_registry().get("moe.a_panel_bytes").value

    try:
        (y_panel, ids, counts), panel_bytes = run(2 * ka * blk, "panel")
        (y_stream, ids_s, _), stream_bytes = run(2 * ka * blk - 1, "stream")
    finally:
        stream._grouped_spmm.clear_cache()
    assert int(np.asarray(counts)[1].sum()) >= 2     # slots change mid-call
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_s))
    np.testing.assert_array_equal(np.asarray(y_panel), np.asarray(y_stream))
    assert (panel_bytes, stream_bytes) == (3 * ka * blk, 0)

