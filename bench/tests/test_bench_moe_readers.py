"""The routed experts' work counts, the MoE scope reduction and the six
MoE readers, on hand-made counts, a synthetic trace and the registry."""
import os

import numpy as np
import pytest

import bench_tiny  # noqa: F401
from bench import moe_scopes
from bench.metrics import (expert_kernel_ms, expert_kernel_roofline,
                           moe_dispatch_ms, moe_plan_s, moe_route_ms,
                           moe_row_fill)
from bench.moe_scopes import moe_scope_of, reduce_moe
from bench.scopes import ScopedEvent
from bench.trace import OPS_LINE
from bench.work_moe import MoEWork, routed_expert_work
from repro import obs

D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
KERNELS = ["_stream_spmm", "_grouped_spmm"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_routed_work_counts_a_small_case_by_hand():
    w = routed_expert_work([[0, 3, 130]], d_model=256, d_ff=512,
                           kept_fraction=0.25, weight_itemsize=4,
                           act_itemsize=2)
    kept = 0.25 * 256 * 512
    assert w.flops == 3 * 2 * 133 * kept
    # two experts got rows: their three matrices' kept weights; each live
    # row read once and written once
    assert w.bytes == 2 * 3 * kept * 4 + 2 * 133 * 256 * 2


def _work(calls):
    counts = [np.array([[[3, 0, 200], [1, 0, 2]]]) for _ in range(calls)]
    return MoEWork(256, 512, 128, 0.25, counts=counts)


def test_row_fill_and_least_time_read_the_last_calls():
    work = _work(4)
    work.counts.insert(0, np.array([[[128, 0, 0], [1, 0, 0]]]))  # warm-up
    assert work.row_fill(4) == pytest.approx(100 * 203 / (3 * 128))
    assert work.row_fill(5) == pytest.approx(100 * (4 * 203 + 128) /
                                             (13 * 128))
    one = routed_expert_work([[3, 0, 200]], d_model=256, d_ff=512,
                             kept_fraction=0.25, weight_itemsize=4,
                             act_itemsize=4)
    least, bound = one.least_time_s(PEAKS["bf16_flops_per_s"],
                                    PEAKS["hbm_bytes_per_s"])
    assert work.least_s(PEAKS["bf16_flops_per_s"], PEAKS["hbm_bytes_per_s"],
                        calls=4) == pytest.approx(4 * least)
    assert bound == "memory"
    assert MoEWork(256, 512, 128, 0.25).row_fill() is None


def ev(plane, name, start, dur, scope=""):
    return ScopedEvent(plane, OPS_LINE, name, float(start), float(dur), scope)


def kernel(plane, name, scope, start, dur):
    return ev(plane, f"%{name} = f32[8,128,128]{{2,1,0}} custom-call("
              "s32[9]{0} %p)", start, dur, scope)


def glue(plane, scope, start, dur):
    return ev(plane, "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)", start,
              dur, scope)


def synthetic():
    # window 0..1000 ns: on device 0 a route (100..150), a dispatch
    # (150..200), three expert kernels (200..500; one a shared-expert
    # kernel under moe.shared), the combine (500..540) and an expert
    # kernel that ends past the window on device 1
    return [
        ScopedEvent(HOST, "python", "bench.window", 0.0, 1000.0),
        glue(D0, "moe.route", 100, 50),
        glue(D0, "moe.dispatch", 150, 50),
        kernel(D0, "_grouped_spmm.1", "moe.experts", 200, 100),
        kernel(D0, "_grouped_spmm.2", "moe.experts", 300, 100),
        glue(D0, "moe.experts", 400, 20),
        kernel(D0, "_stream_spmm.4", "moe.shared", 420, 80),
        glue(D0, "moe.combine", 500, 40),
        glue(D0, "", 540, 60),
        kernel(D1, "_grouped_spmm.1", "moe.experts", 900, 200),
    ]


def test_scope_is_the_outermost_moe_component():
    assert moe_scope_of("jit(step)/moe.experts/ffn.gate/pallas_call") == \
        "moe.experts"
    assert moe_scope_of("jit(step)/moe.shared/ffn.up/x") == "moe.shared"
    assert moe_scope_of("jit(step)/ffn.up/xmoe.experts/y") == ""


def test_scope_seconds_clip_to_the_window_and_split_kernels():
    r = reduce_moe(synthetic(), kernel_names=KERNELS)
    assert r["window_s"] == pytest.approx(1e-6)
    s, k = r["scope_s"], r["kernel_s"]
    assert s["moe.route"] == pytest.approx(50e-9)
    assert s["moe.experts"] == pytest.approx(320e-9)
    assert k["moe.experts"] == pytest.approx(300e-9)
    assert k["moe.shared"] == pytest.approx(80e-9)
    assert "" not in s and "moe.combine" not in k
    with pytest.raises(ValueError):
        reduce_moe(synthetic()[1:], kernel_names=KERNELS)


@pytest.fixture
def record(monkeypatch):
    got = reduce_moe(synthetic(), kernel_names=KERNELS)
    monkeypatch.setattr(moe_scopes, "for_record", lambda rec: got)
    return {"calls": 2, "trace": {"window_s": 1e-6}, "peaks": PEAKS,
            "work": _work(2)}


def test_the_trace_readers(record):
    assert moe_route_ms.read(record) == pytest.approx(50e-9 / 2 * 1e3)
    assert moe_dispatch_ms.read(record) == pytest.approx(90e-9 / 2 * 1e3)
    assert expert_kernel_ms.read(record) == pytest.approx(300e-9 / 2 * 1e3)
    least = record["work"].least_s(PEAKS["bf16_flops_per_s"],
                                   PEAKS["hbm_bytes_per_s"], calls=2)
    assert expert_kernel_roofline.read(record) == pytest.approx(
        100 * least / 300e-9)
    assert moe_row_fill.read(record) == pytest.approx(100 * 203 / 384)


def test_readers_read_nothing_without_the_programs_names(monkeypatch):
    monkeypatch.setattr(moe_scopes, "for_record", lambda rec: None)
    rec = {"calls": 2, "trace": None, "peaks": PEAKS, "work": object()}
    for reader in (moe_route_ms, moe_dispatch_ms, expert_kernel_ms,
                   expert_kernel_roofline, moe_row_fill):
        assert reader.read(rec) is None
    empty = {"window_s": 1e-6, "scope_s": {}, "kernel_s": {}}
    monkeypatch.setattr(moe_scopes, "for_record", lambda rec: empty)
    rec["work"] = _work(2)
    assert moe_route_ms.read(rec) is None
    assert expert_kernel_roofline.read(rec) is None


def test_plan_reader_sums_the_programs_histogram(monkeypatch):
    registry = obs.MetricsRegistry()
    monkeypatch.setattr(obs, "get_registry", lambda: registry)
    assert moe_plan_s.read({}) is None
    for v in (1.5, 2.0):
        registry.histogram("moe.compress_s").observe(v)
    assert moe_plan_s.read({}) == pytest.approx(3.5)


def test_for_record_finds_the_trace_of_its_window(tmp_path, monkeypatch):
    loads = []

    def fake_load(path):
        loads.append(path)
        events = synthetic()
        if "stale" in path:
            events[0] = ScopedEvent(HOST, "python", "bench.window", 0.0,
                                    999.0)
        return events

    monkeypatch.setattr(moe_scopes, "load_events", fake_load)
    monkeypatch.setattr(moe_scopes, "kernel_names_of", lambda w: KERNELS)
    monkeypatch.setattr(moe_scopes, "_READ", {})
    for i, cell in enumerate(("fresh", "stale")):
        d = tmp_path / cell / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        p = d / "h.xplane.pb"
        p.write_bytes(b"")
        os.utime(p, (1000 + i, 1000 + i))              # stale is newer
    rec = {"trace": {"window_s": 1e-6}}
    got = moe_scopes.for_record(rec, traces=tmp_path)
    assert got["kernel_s"]["moe.experts"] == pytest.approx(300e-9)
    assert len(loads) == 2
    assert moe_scopes.for_record(rec, traces=tmp_path) is got
    assert len(loads) == 2
    monkeypatch.setattr(moe_scopes, "load_events", lambda path: [])
    monkeypatch.setattr(moe_scopes, "_READ", {})
    assert moe_scopes.for_record(rec, traces=tmp_path) is None
