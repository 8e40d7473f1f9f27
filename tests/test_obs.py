"""repro.obs: span tracing, metrics registry, and the instrumented seams."""
import json
import threading
import tracemalloc

import numpy as np
import pytest

import repro.obs as obs
from repro.obs import trace as trace_mod
from repro.obs.__main__ import validate_chrome
from repro.obs.metrics import Histogram, MetricsRegistry


@pytest.fixture
def tracing():
    """Tracing on, clean tracer; restores env-driven behaviour after."""
    tracer = obs.get_tracer()
    tracer.clear()
    obs.enable()
    yield tracer
    trace_mod._reset_override()
    tracer.clear()


@pytest.fixture
def no_tracing():
    obs.disable()
    yield obs.get_tracer()
    trace_mod._reset_override()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_nesting_builds_parent_chain(tracing):
    with obs.span("outer", a=1):
        with obs.span("middle"):
            with obs.span("inner"):
                pass
    spans = {s.name: s for s in tracing.spans()}
    assert set(spans) == {"outer", "middle", "inner"}
    assert spans["outer"].parent is None
    assert spans["middle"].parent == spans["outer"].sid
    assert spans["inner"].parent == spans["middle"].sid
    assert spans["outer"].attrs == {"a": 1}
    # children completed inside the parent's window
    assert spans["outer"].t0_ns <= spans["inner"].t0_ns
    assert spans["inner"].dur_ns <= spans["outer"].dur_ns


def test_span_exception_safety(tracing):
    """A raising body still records the span (error-tagged) and unwinds the
    stack so the next span is not parented under the dead one."""
    with pytest.raises(ValueError):
        with obs.span("failing"):
            raise ValueError("boom")
    with obs.span("after"):
        pass
    spans = {s.name: s for s in tracing.spans()}
    assert spans["failing"].attrs["error"] == "ValueError"
    assert spans["after"].parent is None
    assert tracing.current_span() is None


def test_span_set_attaches_mid_span_attrs(tracing):
    with obs.span("s") as sp:
        sp.set(result=42)
    (rec,) = tracing.spans()
    assert rec.attrs["result"] == 42


def test_traced_decorator(tracing):
    @obs.traced("deco.fn", tag="x")
    def f(v):
        return v + 1

    assert f(1) == 2
    (rec,) = tracing.spans()
    assert rec.name == "deco.fn"
    assert rec.attrs == {"tag": "x"}


def test_disabled_span_is_shared_noop_with_no_retained_allocations(
        no_tracing):
    """With REPRO_TRACE off, span() returns one shared object and retains
    nothing — the hot-path cost is a dict lookup, not an allocation."""
    assert obs.span("a") is obs.span("b", k=1) is trace_mod._NOOP
    before = len(no_tracing)

    def burst():
        for i in range(500):
            with obs.span("hot", i=i):
                pass

    burst()  # warm any lazy interning
    tracemalloc.start()
    s0 = tracemalloc.take_snapshot()
    burst()
    s1 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    retained = sum(d.size_diff for d in s1.compare_to(s0, "filename")
                   if "trace.py" in (d.traceback[0].filename or ""))
    assert retained == 0
    assert len(no_tracing) == before


def test_ring_buffer_bounds_memory():
    tr = trace_mod.Tracer(capacity=8)
    for i in range(20):
        tr.record(f"s{i}", i, 1)
    assert len(tr) == 8
    assert tr.dropped == 12
    assert [s.name for s in tr.spans()] == [f"s{i}" for i in range(12, 20)]


def test_chrome_export_schema_and_roundtrip(tracing, tmp_path):
    with obs.span("plan.phase1", dataflow="auto"):
        with obs.span("plan.select"):
            pass
    native = tmp_path / "t.jsonl"
    chrome = tmp_path / "t.chrome.json"
    n = tracing.save(str(native))
    tracing.save_chrome(str(chrome))
    assert n == 2

    # native round-trip preserves every field
    back = trace_mod.read_spans(str(native))
    orig = tracing.spans()
    assert [(s.name, s.sid, s.parent, s.t0_ns, s.dur_ns, s.attrs)
            for s in back] == \
        [(s.name, s.sid, s.parent, s.t0_ns, s.dur_ns, s.attrs)
         for s in orig]

    # exported doc passes the CI schema gate and carries the tree
    doc = json.loads(chrome.read_text())
    assert validate_chrome(doc) == []
    events = {e["name"]: e for e in doc["traceEvents"]}
    assert events["plan.select"]["args"]["parent"] == \
        events["plan.phase1"]["args"]["sid"]
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"
        assert ev["dur"] >= 0
        assert ev["cat"] == "plan"


def test_validate_chrome_rejects_bad_docs():
    assert validate_chrome([]) != []
    assert validate_chrome({"traceEvents": [{"ph": "X"}]}) != []
    missing_parent = {"traceEvents": [
        {"ph": "X", "ts": 0, "dur": 1, "pid": 1, "tid": 1, "name": "a",
         "args": {"sid": 5, "parent": 9}}]}
    assert any("unbalanced" in e for e in validate_chrome(missing_parent))


def test_summarize_table(tracing):
    for i in range(4):
        tr = obs.get_tracer()
        tr.record("plan.x", 0, (i + 1) * 1000)
    table = obs.summarize(tracing.spans())
    assert "plan.x" in table
    assert "count" in table and "p99_us" in table


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_counter_gauge_snapshot():
    reg = MetricsRegistry()
    reg.counter("cache.hits").inc()
    reg.counter("cache.hits").inc(2)
    reg.gauge("dist.ici_bytes").set(128.0)
    snap = reg.snapshot()
    assert snap["cache.hits"] == {"type": "counter", "value": 3.0}
    assert snap["dist.ici_bytes"]["value"] == 128.0
    assert json.loads(reg.to_json())["cache.hits"]["value"] == 3.0
    # prefix filtering
    assert list(reg.snapshot(prefix="cache.")) == ["cache.hits"]


def test_registry_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_histogram_percentiles_match_numpy_within_bucket_ratio():
    """Bucketed quantiles land within one log-bucket ratio of numpy's
    exact percentiles (the documented resolution contract)."""
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-7.0, sigma=1.5, size=5000)  # latency-like
    h = Histogram("serve.latency.s")
    for v in vals:
        h.observe(float(v))
    ratio = h.buckets[1] / h.buckets[0]
    for q in (0.50, 0.90, 0.99):
        exact = float(np.percentile(vals, q * 100))
        est = h.quantile(q)
        assert exact / ratio <= est <= exact * ratio, (q, exact, est)
    assert h.count == len(vals)
    assert h.sum == pytest.approx(float(vals.sum()))
    snap = h.snapshot()
    assert snap["min"] == pytest.approx(float(vals.min()))
    assert snap["max"] == pytest.approx(float(vals.max()))
    assert snap["p50"] == h.quantile(0.50)


@pytest.mark.parametrize("n", [1, 7, 10, 5000])
def test_histogram_quantiles_are_exact_nearest_rank(n):
    """Quantiles are numpy's ``inverted_cdf`` of the retained samples,
    exactly: a value that was observed, not a bucket's upper edge."""
    from repro.obs.metrics import SAMPLES

    rng = np.random.default_rng(n)
    vals = rng.lognormal(mean=-7.0, sigma=1.5, size=n)
    h = Histogram("serve.latency.s")
    for v in vals:
        h.observe(float(v))
    kept = vals[-SAMPLES:]
    for q in (0.0, 0.1, 0.5, 0.9, 0.95, 0.99, 1.0):
        assert h.quantile(q) == float(
            np.quantile(kept, q, method="inverted_cdf")), q
    assert sum(c for _, c in h.snapshot()["buckets"]) == n


def test_histogram_quantiles_cover_the_last_samples_only():
    h = Histogram("s", samples=4)
    for v in (100.0, 1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.quantile(1.0) == 4.0 and h.quantile(0.0) == 1.0
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["max"] == 100.0
    assert snap["buckets"][-1] == [pytest.approx(100.0), 1]


def test_metrics_thread_safety_smoke():
    reg = MetricsRegistry()

    def work():
        for _ in range(1000):
            reg.counter("c").inc()
            reg.histogram("h").observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("c").value == 8000
    assert reg.histogram("h").count == 8000


def test_tracer_thread_spans_do_not_cross_parent(tracing):
    """Span stacks are per-thread: concurrent spans never parent across
    threads."""
    def worker(tag):
        for _ in range(50):
            with obs.span(f"t.{tag}"):
                pass

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(s.parent is None for s in tracing.spans())
    assert len(tracing) == 200


# ---------------------------------------------------------------------------
# instrumented seams
# ---------------------------------------------------------------------------


def test_flexagon_plan_emits_phase1_spans_and_metrics(tracing):
    from repro import flexagon_plan
    from repro.core import random_sparse_dense

    reg = obs.get_registry()
    builds0 = reg.value("plan.builds")
    rng = np.random.default_rng(0)
    a = random_sparse_dense(rng, (32, 32), density=0.3, block_shape=(8, 8))
    b = random_sparse_dense(rng, (32, 48), density=0.6, block_shape=(8, 8))
    plan = flexagon_plan(a, b, block_shape=(8, 8, 8))
    spans = {s.name: s for s in tracing.spans()}
    assert {"plan.phase1", "plan.select", "plan.tables",
            "plan.prepare"} <= set(spans)
    assert spans["plan.select"].parent == spans["plan.phase1"].sid
    assert spans["plan.phase1"].attrs["chosen"] == plan.dataflow
    assert reg.value("plan.builds") == builds0 + 1
    assert reg.get("policy.select_s").count >= 1


def test_tiled_apply_span_carries_tier_traffic(tracing):
    """A tiled apply is jit-compatible, so it names its device work with a
    ``jax.named_scope`` in the lowered program; it records no host span
    (under jit that would time tracing) and prices no ASIC tier traffic."""
    import jax

    from repro import MemoryBudget, TiledPlan, flexagon_plan
    from repro.core import random_sparse_dense

    rng = np.random.default_rng(0)
    a = random_sparse_dense(rng, (64, 64), density=0.4, block_shape=(16, 16))
    b = random_sparse_dense(rng, (64, 64), density=0.6, block_shape=(16, 16))
    plan = flexagon_plan(a, b, block_shape=(16, 16, 16),
                         memory_budget=MemoryBudget(l1_bytes=4 << 10,
                                                    l2_bytes=8 << 10))
    assert isinstance(plan, TiledPlan)
    tracing.clear()
    text = jax.jit(plan.apply).lower(a, b).as_text(debug_info=True)
    assert "memory.tiled.apply" in text
    assert not tracing.spans()
    assert not any(n.startswith("tier.") for n in obs.get_registry().names())


def test_plan_cache_counts_into_global_registry():
    from repro.api import PlanCache
    from repro.core import random_sparse_dense

    reg = obs.get_registry()
    h0, m0 = reg.value("cache.hits"), reg.value("cache.misses")
    rng = np.random.default_rng(0)
    a = random_sparse_dense(rng, (32, 32), density=0.3, block_shape=(8, 8))
    b = random_sparse_dense(rng, (32, 32), density=0.6, block_shape=(8, 8))
    cache = PlanCache()
    cache.get(a, b, block_shape=(8, 8, 8))
    cache.get(a, b, block_shape=(8, 8, 8))
    assert reg.value("cache.misses") == m0 + 1
    assert reg.value("cache.hits") == h0 + 1


# ---------------------------------------------------------------------------
# ServeEngine end-to-end
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served_engine():
    """One engine run with tracing on: 3 requests through 2 slots."""
    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve.engine import Request, ServeEngine

    tracer = obs.get_tracer()
    tracer.clear()
    obs.enable()
    try:
        cfg = get_config("smollm-360m", smoke=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        eng = ServeEngine(model, params, slots=2, max_seq=64)
        rng = np.random.default_rng(0)
        for rid in range(3):
            prompt = rng.integers(0, cfg.vocab, size=5)
            eng.submit(Request(rid, prompt, max_new_tokens=4))
        results = eng.run_to_completion()
        spans = tracer.spans()
    finally:
        trace_mod._reset_override()
        tracer.clear()
    return eng, results, spans


def test_serve_latency_histograms_populated(served_engine):
    eng, results, _ = served_engine
    assert len(results) == 3
    lat = eng.latency_stats()
    for name in ("serve.latency.queue_s", "serve.latency.prefill_s",
                 "serve.latency.decode_step_s", "serve.latency.request_s"):
        assert name in lat, name
        assert lat[name]["count"] >= 1
        assert lat[name]["p50"] > 0
        assert lat[name]["p99"] >= lat[name]["p50"]
    assert lat["serve.latency.request_s"]["count"] == 3
    assert eng.stats["completed"] == 3
    assert eng.stats["decode_steps"] == \
        lat["serve.latency.decode_step_s"]["count"]


def test_serve_request_span_trees(served_engine):
    _, _, spans = served_engine
    requests = [s for s in spans if s.name == "serve.request"]
    prefills = [s for s in spans if s.name == "serve.prefill"]
    decodes = [s for s in spans if s.name == "serve.decode_step"]
    assert len(requests) == 3 and len(prefills) == 3
    assert decodes, "fused decode steps must be traced"
    # every request roots its own tree: exactly one prefill child each
    by_parent = {}
    for p in prefills:
        by_parent.setdefault(p.parent, []).append(p)
    for req in requests:
        assert req.parent is None
        children = by_parent.get(req.sid, [])
        assert len(children) == 1
        assert children[0].attrs["rid"] == req.attrs["rid"]
        assert req.attrs["new_tokens"] == 4


def test_serve_stats_property_returns_independent_snapshots(served_engine):
    """Satellite regression: mutating live policy/cache stats after a
    snapshot must not rewrite previously returned snapshots."""
    eng, _, _ = served_engine
    s1 = eng.stats
    s2 = eng.stats
    assert s1 is not s2 and s1 == s2
    s1["completed"] = 10 ** 9
    assert eng.stats["completed"] == s2["completed"] != s1["completed"]


def test_sync_plan_stats_deep_copies_nested_dicts():
    """The original aliasing bug: _sync_plan_stats copied policy stats
    shallowly, so later nested-dict mutation leaked into old snapshots."""
    import copy

    class _Policy:
        def __init__(self):
            self.stats = {"nested": {"measurements": 0}}

    class _FFN:
        plan_builds = 1
        plan_hits = 2
        backend = "reference"
        cache_stats = {"hits": 0, "inner": {"deep": 0}}

        def __init__(self):
            self.policy = _Policy()

    from repro.serve.engine import ServeEngine

    eng = ServeEngine.__new__(ServeEngine)   # stats plumbing only
    eng.metrics = obs.MetricsRegistry()
    eng._plan_stats = {"plan_builds": 0, "plan_hits": 0}
    eng.sparse_ffn = _FFN()
    eng.decode_ffn = None
    eng._sync_plan_stats()
    snap = eng.stats
    before = copy.deepcopy(snap)
    # mutate the live nested dicts the old code aliased
    eng.sparse_ffn.policy.stats["nested"]["measurements"] = 999
    eng.sparse_ffn.cache_stats["inner"]["deep"] = 999
    assert snap == before, "snapshot must not alias live policy/cache dicts"
