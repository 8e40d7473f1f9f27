"""Serving engine: correctness vs reference decode, continuous batching."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve.engine import Request, ServeEngine


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("smollm-360m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _reference_greedy(model, params, prompt, n_new, max_seq=64,
                      dtype=jnp.bfloat16):
    cache = model.init_cache(1, max_seq, dtype)
    logits, cache = model.prefill(params, jnp.asarray(prompt)[None], cache)
    toks = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(n_new - 1):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([[toks[-1]]], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks


def test_engine_matches_reference(model_and_params):
    cfg, model, params = model_and_params
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=7)
    eng = ServeEngine(model, params, slots=3, max_seq=64)
    eng.submit(Request(0, prompt, max_new_tokens=6))
    out = eng.run_to_completion()[0]
    assert out == _reference_greedy(model, params, prompt, 6)


@pytest.mark.slow
def test_continuous_batching_mixed_lengths(model_and_params):
    """More requests than slots, different prompt lengths and progress —
    every request must still match its isolated reference decode."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, size=int(n))
               for n in rng.integers(3, 12, size=6)]
    eng = ServeEngine(model, params, slots=2, max_seq=64)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid, p, max_new_tokens=4))
    results = eng.run_to_completion()
    assert len(results) == len(prompts)
    assert eng.stats["completed"] == len(prompts)
    for rid, p in enumerate(prompts):
        assert results[rid] == _reference_greedy(model, params, p, 4), rid


def test_plans_built_at_admission_reused_at_decode(model_and_params):
    """A plan-backed sparse FFN attached to the engine is specialized for
    the fused decode shape at construction and per prompt length at
    admission; decode steps are pure cache hits."""
    import jax.numpy as jnp
    from repro.models.ffn import ffn_init
    from repro.models.sparse_linear import compress_ffn
    from repro.configs.base import ModelConfig

    cfg, model, params = model_and_params
    fcfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                       n_heads=4, d_ff=96, vocab=64, ffn_block_sparsity=0.4)
    fparams = ffn_init(jax.random.PRNGKey(0), fcfg)
    fparams["block_mask"] = (jax.random.uniform(
        jax.random.PRNGKey(9), (4, 6)) > 0.4).astype(jnp.float32)
    comp = compress_ffn(fparams, tokens=2, block=16)      # decode shape

    rng = np.random.default_rng(3)
    eng = ServeEngine(model, params, slots=2, max_seq=64, sparse_ffn=comp)
    assert eng.decode_ffn is comp.specialize(2)           # decode shape ready
    builds_after_init = comp.plan_builds
    for rid in range(3):
        eng.submit(Request(rid, rng.integers(0, cfg.vocab, size=5),
                           max_new_tokens=4))
    eng.run_to_completion()
    # admission planned exactly one new shape (prompt length 5); the other
    # two same-length admissions were cache hits, decode never re-planned
    assert comp.plan_builds == builds_after_init + 1
    assert comp.plan_hits >= 2
    assert eng.stats["plan_builds"] == comp.plan_builds
    assert eng.stats["plan_hits"] == comp.plan_hits


def test_moe_decode_strategy_planned_once():
    """An auto-strategy MoE model gets its dispatch strategy planned once
    for the fused decode shape; the jitted decode closure runs with it
    pinned (no per-step selector) and still matches reference decode."""
    import dataclasses

    from repro.models.moe import select_moe_strategy

    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, strategy="auto"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, slots=1, max_seq=64)
    assert eng.moe_plan is not None and eng.moe_plan.tokens == 1
    assert eng.moe_plan.strategy == select_moe_strategy(
        1, cfg.d_model, cfg.d_ff, cfg.moe.num_experts, cfg.moe.top_k)
    prompt = np.random.default_rng(5).integers(0, cfg.vocab, size=4)
    eng.submit(Request(0, prompt, max_new_tokens=3))
    out = eng.run_to_completion()[0]
    # slots=1 makes the pinned decode shape equal the reference's, so the
    # pinned strategy is exactly what auto re-derives — outputs identical
    assert out == _reference_greedy(model, params, prompt, 3)


def test_eos_frees_slot(model_and_params):
    cfg, model, params = model_and_params
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab, size=5)
    ref = _reference_greedy(model, params, prompt, 8)
    eos = ref[2]
    eng = ServeEngine(model, params, slots=1, max_seq=64)
    eng.submit(Request(0, prompt, max_new_tokens=8, eos_id=eos))
    out = eng.run_to_completion()[0]
    assert out == ref[:3]       # stops right after emitting eos


# ---------------------------------------------------------------------------
# Cache-write regressions (engine.py prefill/step fixes)
# ---------------------------------------------------------------------------


class _InitCacheSpy:
    """Delegating model wrapper that records every ``init_cache`` call."""

    def __init__(self, model):
        self._model = model
        self.calls = []

    def __getattr__(self, name):
        return getattr(self._model, name)

    def init_cache(self, batch, max_seq, dtype=jnp.bfloat16):
        self.calls.append((batch, dtype))
        return self._model.init_cache(batch, max_seq, dtype)


def test_prefill_threads_engine_dtype(model_and_params):
    """Every cache the engine builds — the slot cache AND the batch-1
    prefill caches — must carry the engine dtype; prefill silently
    allocating at the model default and casting at write time is the bug."""
    cfg, model, params = model_and_params
    spy = _InitCacheSpy(model)
    eng = ServeEngine(spy, params, slots=2, max_seq=64, dtype=jnp.float32)
    rng = np.random.default_rng(4)
    eng.submit(Request(0, rng.integers(0, cfg.vocab, size=5),
                       max_new_tokens=3))
    eng.run_to_completion()
    assert len(spy.calls) >= 2           # slot cache + >= 1 prefill cache
    assert all(dt == jnp.float32 for _, dt in spy.calls), spy.calls


def test_mixed_dtype_serve_round_trip(model_and_params):
    """A float32 engine must match the float32 reference decode exactly —
    the whole prefill/decode path runs at the engine dtype."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab, size=int(s)) for s in (5, 9)]
    eng = ServeEngine(model, params, slots=2, max_seq=64, dtype=jnp.float32)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid, p, max_new_tokens=5))
    results = eng.run_to_completion()
    for rid, p in enumerate(prompts):
        assert results[rid] == _reference_greedy(model, params, p, 5,
                                                 dtype=jnp.float32), rid


class _OddCacheLeafModel:
    """Minimal decode surface whose cache hides a leaf with no detectable
    batch dim (shape ``(2 * batch, 3)``) — a silent skip would decode
    against a stale prefix with no error at all."""

    vocab = 17

    def init_cache(self, batch, max_seq, dtype=jnp.bfloat16):
        return {"layers": {"k": jnp.zeros((batch, max_seq, 4), dtype),
                           "odd": jnp.zeros((batch * 2, 3), dtype)},
                "pos": jnp.zeros((batch,), jnp.int32)}

    def prefill(self, params, tokens, cache):
        b, s = tokens.shape
        return jnp.ones((b, s, self.vocab)), cache

    def decode_step(self, params, cache, tokens):
        return (jnp.ones((tokens.shape[0], 1, self.vocab)),
                {"layers": cache["layers"], "pos": cache["pos"] + 1})


def test_unmatched_cache_leaf_fails_loud():
    model = _OddCacheLeafModel()
    eng = ServeEngine(model, {}, slots=3, max_seq=16)
    with pytest.raises(ValueError, match="batch dim"):
        eng.submit(Request(0, np.asarray([1, 2, 3]), max_new_tokens=2))


def test_slot_reuse_parity(model_and_params):
    """A free slot must not drift while other slots decode, and the same
    request decoded in a reused slot must match a fresh engine exactly."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(8)
    p0 = rng.integers(0, cfg.vocab, size=6)
    p1 = rng.integers(0, cfg.vocab, size=9)

    eng = ServeEngine(model, params, slots=2, max_seq=64)
    eng.submit(Request(0, p0, max_new_tokens=6))
    eng.run_to_completion()
    # slot 1 sat free through 6 fused steps; its state must not have drifted
    pos = np.asarray(eng.cache["pos"])
    assert (pos == 0).all(), pos

    # the reused slot replays the request identically to a fresh engine
    eng.submit(Request(1, p1, max_new_tokens=6))
    reused = eng.run_to_completion()[1]
    fresh = ServeEngine(model, params, slots=2, max_seq=64)
    fresh.submit(Request(1, p1, max_new_tokens=6))
    assert reused == fresh.run_to_completion()[1]
    assert reused == _reference_greedy(model, params, p1, 6)


def test_verify_plans_audits_live_cache(model_and_params):
    """``ServeEngine.verify_plans`` runs the full analysis layer — plan
    invariants + the static schedule checker — over the sparse FFN's LRU
    as it currently stands, and flags a corrupted re-admission."""
    import dataclasses

    from repro.models.ffn import ffn_init
    from repro.models.sparse_linear import compress_ffn
    from repro.configs.base import ModelConfig

    cfg, model, params = model_and_params
    fcfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                       n_heads=4, d_ff=96, vocab=64, ffn_block_sparsity=0.4)
    fparams = ffn_init(jax.random.PRNGKey(0), fcfg)
    fparams["block_mask"] = (jax.random.uniform(
        jax.random.PRNGKey(9), (4, 6)) > 0.4).astype(jnp.float32)
    comp = compress_ffn(fparams, tokens=2, block=16, backend="pallas")

    rng = np.random.default_rng(3)
    eng = ServeEngine(model, params, slots=2, max_seq=64, sparse_ffn=comp)
    eng.submit(Request(0, rng.integers(0, cfg.vocab, size=5),
                       max_new_tokens=4))
    eng.run_to_completion()
    assert eng.verify_plans() == []          # live cache is clean

    # corrupt one cached entry the way a buggy re-admission would: same
    # key, schedule swapped for another entry's (or dropped entirely)
    cache = comp.plan_cache
    key, plan = next((k, p) for k, p in cache._plans.items()
                     if getattr(p, "aux", None)
                     and "stream_schedule" in p.aux)
    stripped = dataclasses.replace(
        plan, aux={k: v for k, v in plan.aux.items()
                   if k != "stream_schedule"})
    cache._plans[key] = stripped
    codes = {d.code for d in eng.verify_plans()}
    assert "schedule-missing" in codes, codes

    no_ffn = ServeEngine(model, params, slots=1, max_seq=16)
    assert no_ffn.verify_plans() == []


def test_engine_surfaces_policy_stats():
    from repro.backends import AutotunePolicy
    from repro.configs.base import ModelConfig
    from repro.models.ffn import ffn_init
    from repro.models.sparse_linear import compress_ffn

    cfg = get_config("smollm-360m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    fcfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                       n_heads=4, d_ff=96, vocab=64, ffn_block_sparsity=0.4)
    fparams = ffn_init(jax.random.PRNGKey(0), fcfg)
    fparams["block_mask"] = (jax.random.uniform(
        jax.random.PRNGKey(9), (4, 6)) > 0.4).astype(jnp.float32)
    pol = AutotunePolicy(reps=1, maxsize=8)
    comp = compress_ffn(fparams, tokens=2, block=16, policy=pol)
    eng = ServeEngine(model, params, slots=2, max_seq=64, sparse_ffn=comp)
    stats = eng.stats["policy"]
    assert stats["name"] == "autotune"
    assert stats["measurements"] == pol.measurements >= 1
    assert {"hits", "misses", "evictions", "size", "maxsize"} <= stats.keys()
