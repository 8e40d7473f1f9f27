"""The scope reduction and its readers on a small synthetic trace."""
import struct

import pytest

import bench_tiny  # noqa: F401
from bench import scopes
from bench.metrics import (down_kernel_ms, gate_kernel_ms, in_call_idle_share,
                           kernel_ns_per_pair, plan_phase1_s, up_kernel_ms,
                           weight_pack_s)
from bench.scopes import ScopedEvent, reduce_scopes, scope_of
from bench.trace import MODULES_LINE, OPS_LINE, reduce_trace
from repro import obs

D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
KERNELS = ["_stream_spmm", "_stream_panel_spmm"]


def kernel(plane, n, scope, start, dur):
    return ScopedEvent(plane, OPS_LINE, f"%_stream_spmm.{n} = f32[8,128,128]"
                       "{2,1,0} custom-call(s32[9]{0} %p)", float(start),
                       float(dur), scope)


def op(plane, name, start, dur):
    return ScopedEvent(plane, OPS_LINE, f"%{name} = f32[8]{{0}} fusion("
                       "f32[8]{0} %p)", float(start), float(dur))


def module(start, dur, plane=D0):
    return ScopedEvent(plane, MODULES_LINE, "jit_step", float(start),
                       float(dur))


def host(name, start, dur, thread="python"):
    return ScopedEvent(HOST, thread, name, float(start), float(dur))


def synthetic():
    # window 0..1000 ns.  Device 0 runs two calls: 100..400 (gate 100..200,
    # glue 250..300, up 300..400) and 600..900 (down 600..700, gate
    # 800..900, a scope-less kernel 880..900 overlapping it).  Device 1
    # runs a down kernel that ends past the window.
    return [
        host("bench.window", 0, 1000),
        host("bench.wait", 400, 200),
        host("gc.collect", 450, 100, thread="main"),
        host("gc.collect", 560, 20, thread="main"),
        host("tpu.enqueue", 0, 80, thread="runtime"),
        module(100, 300), module(600, 300),
        kernel(D0, 1, "ffn.gate", 100, 100),
        op(D0, "fusion.2", 250, 50),
        kernel(D0, 2, "ffn.up", 300, 100),
        kernel(D0, 3, "ffn.down", 600, 100),
        kernel(D0, 1, "ffn.gate", 800, 100),
        kernel(D0, 4, "", 880, 20),
        kernel(D1, 3, "ffn.down", 900, 200),
    ]


def test_kernel_seconds_by_scope_sum_to_kernel_seconds():
    s = reduce_scopes(synthetic(), kernel_names=KERNELS)
    by = s["kernel_s_by_scope"]
    assert by["ffn.gate"] == pytest.approx(200e-9)
    assert by["ffn.up"] == pytest.approx(100e-9)
    assert by["ffn.down"] == pytest.approx(200e-9)       # 100 + 100 clipped
    assert by[""] == pytest.approx(20e-9)
    t = reduce_trace(synthetic(), kernel_names=KERNELS)
    assert sum(by.values()) == pytest.approx(t["kernel_s"])
    assert s["window_s"] == t["window_s"]


def test_idle_inside_calls_is_module_time_less_the_ops_union():
    s = reduce_scopes(synthetic(), kernel_names=KERNELS)
    # call 1: 300 ns, ops cover 100 + 50 + 100; call 2: 300, ops cover 200
    assert s["in_call_idle_s"] == pytest.approx((50 + 100) * 1e-9)
    t = reduce_trace(synthetic(), kernel_names=KERNELS)
    assert s["in_call_idle_s"] <= t["idle_share"] * t["window_s"]


def test_long_gaps_name_the_host_events_that_overlap_them():
    s = reduce_scopes(synthetic(), kernel_names=KERNELS, long_gaps=2,
                      per_gap=2)
    # device 0 gaps: 0..100, 200..250, 400..600, 700..800, 900..1000
    longest, second = s["long_gap_host"]
    assert longest["gap_ms"] == pytest.approx(200e-6)
    assert longest["at_s"] == pytest.approx(400e-9)
    assert longest["host"] == [["bench.wait", "python", pytest.approx(200e-6)],
                               ["gc.collect", "main", pytest.approx(120e-6)]]
    assert second["gap_ms"] == pytest.approx(100e-6)
    assert second["at_s"] == pytest.approx(0.0)
    assert second["host"] == [["tpu.enqueue", "runtime",
                               pytest.approx(80e-6)]]


def test_scope_is_the_innermost_ffn_component_of_the_op_name():
    assert scope_of("jit(step)/jit(main)/ffn.gate/jit(_stream_spmm)/"
                    "pallas_call") == "ffn.gate"
    assert scope_of("jit(step)/ffn.act/mul") == "ffn.act"
    assert scope_of("jit(step)/ffnx.gate/dot") == ""
    assert scope_of("") == ""


def test_a_trace_without_the_window_or_device_ops_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        reduce_scopes([module(0, 1)], kernel_names=KERNELS)
    with pytest.raises(ValueError, match="no device operations"):
        reduce_scopes([host("bench.window", 0, 10)], kernel_names=KERNELS)


@pytest.fixture
def record(monkeypatch):
    """A traced record of 4 calls whose trace reduces to ``synthetic()``."""
    red = reduce_scopes(synthetic(), kernel_names=KERNELS)
    monkeypatch.setattr(scopes, "for_record",
                        lambda rec: red if rec.get("trace") else None)
    t = reduce_trace(synthetic(), kernel_names=KERNELS)
    return {"calls": 4, "window_s": t["window_s"], "trace": t}


@pytest.fixture
def registry(monkeypatch):
    reg = obs.MetricsRegistry()
    monkeypatch.setattr(obs, "get_registry", lambda: reg)
    return reg


@pytest.mark.parametrize("reader,ms", [(gate_kernel_ms, 200e-6 / 4),
                                       (up_kernel_ms, 100e-6 / 4),
                                       (down_kernel_ms, 200e-6 / 4)])
def test_per_matmul_kernel_readers(record, reader, ms):
    assert reader.read(record) == pytest.approx(ms)
    assert reader.read(dict(record, trace=None)) is None


def test_a_trace_without_the_scope_reads_nothing(monkeypatch, record):
    red = dict(scopes.for_record(record), kernel_s_by_scope={"": 1e-6})
    monkeypatch.setattr(scopes, "for_record", lambda rec: red)
    assert gate_kernel_ms.read(record) is None


def test_in_call_idle_share_reader(record):
    assert in_call_idle_share.read(record) == pytest.approx(15.0)
    assert in_call_idle_share.read(dict(record, trace=None)) is None


def test_kernel_ns_per_pair_reads_the_programs_own_count(record, registry):
    assert kernel_ns_per_pair.read(record) is None       # no count: parent
    registry.gauge("ffn.block_pairs").set(10)
    kernel_s = record["trace"]["kernel_s"]
    assert kernel_ns_per_pair.read(record) == pytest.approx(
        kernel_s / (4 * 10) * 1e9)


def test_set_up_readers_sum_the_programs_histograms(registry):
    assert plan_phase1_s.read({}) is None
    assert weight_pack_s.read({}) is None
    for v in (1.5, 0.25):
        registry.histogram("plan.build_s").observe(v)
    registry.histogram("ffn.mask_s").observe(2.0)
    assert weight_pack_s.read({}) is None                 # no pack yet
    for v in (0.5, 0.5, 0.25):
        registry.histogram("ffn.pack_s").observe(v)
    assert plan_phase1_s.read({}) == pytest.approx(1.75)
    assert weight_pack_s.read({}) == pytest.approx(3.25)


def test_for_record_finds_the_trace_of_its_window(tmp_path, monkeypatch):
    """The newest trace whose window matches the record's is read, once."""
    import os

    loads = []

    def fake_load(path):
        loads.append(path)
        events = synthetic()
        if "stale" in path:
            events[0] = host("bench.window", 0, 999)
        return events

    monkeypatch.setattr(scopes, "load_events", fake_load)
    monkeypatch.setattr(scopes, "kernel_names_of", lambda w: KERNELS)
    monkeypatch.setattr(scopes, "_READ", {})
    paths = []
    for i, cell in enumerate(("fresh", "stale")):
        d = tmp_path / cell / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        p = d / "h.xplane.pb"
        p.write_bytes(b"")
        os.utime(p, (1000 + i, 1000 + i))              # stale is newer
        paths.append(str(p))
    t = reduce_trace(synthetic(), kernel_names=KERNELS)
    got = scopes.for_record({"trace": t}, traces=tmp_path)
    assert got["kernel_s_by_scope"]["ffn.up"] == pytest.approx(100e-9)
    assert loads == paths[::-1]
    assert scopes.for_record({"trace": t}, traces=tmp_path) is got
    assert len(loads) == 2
    assert scopes.for_record({"trace": None}, traces=tmp_path) is None
    # a broken trace is passed over, never raised
    monkeypatch.setattr(scopes, "load_events", lambda path: [])
    monkeypatch.setattr(scopes, "_READ", {})
    assert scopes.for_record({"trace": t}, traces=tmp_path) is None


def test_kernel_names_come_from_the_cells_configuration():
    for cell in ("mixtral-ffn.decode16", "chameleon-ffn.decode32"):
        assert scopes.kernel_names_of(cell) == KERNELS


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(num, value):
    """One protobuf field: a varint, a double, or length-delimited."""
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


GATE = ("%_stream_spmm.1 = f32[8,128,128]{2,1,0} custom-call(s32[9]{0} %p)")
UP = ("%_stream_spmm.2 = f32[8,128,128]{2,1,0} custom-call(s32[9]{0} %p)")


def _entry(key, msg):
    return _f(1, key) + _f(2, msg)


def _xspace():
    """A TPU-like XSpace: a device plane whose kernel events keep their
    op_name in their metadata (as a string, and as a reference to an
    interned string), and a host plane holding the window."""
    stat_md = b"".join(_f(5, _entry(k, _f(1, k) + _f(2, n))) for k, n in (
        (1, "tf_op"), (2, "flops"), (3, "jit(step)/ffn.up/pallas_call")))
    gate_md = _f(1, 1) + _f(2, GATE) + \
        _f(5, _f(1, 1) + _f(5, "jit(step)/jit(main)/ffn.gate/pallas_call")) + \
        _f(5, _f(1, 2) + _f(2, 3.0))
    up_md = _f(1, 2) + _f(2, UP) + _f(5, _f(1, 1) + _f(7, 3))
    ev_md = _f(4, _entry(1, gate_md)) + _f(4, _entry(2, up_md)) + \
        _f(4, _entry(3, _f(1, 3) + _f(2, "jit_step")))

    def line(name, *events):      # (metadata id, offset ps, duration ps)
        return _f(3, _f(2, name) + _f(3, 1000) + b"".join(
            _f(4, _f(1, m) + _f(2, o) + _f(3, d)) for m, o, d in events))

    device = _f(1, 7) + _f(2, D0) + \
        line(OPS_LINE, (1, 100_000, 50_000), (2, 200_000, 30_000)) + \
        line(MODULES_LINE, (3, 100_000, 130_000)) + ev_md + stat_md
    host_plane = _f(1, 8) + _f(2, HOST) + line("python", (1, 0, 400_000)) + \
        _f(4, _entry(1, _f(1, 1) + _f(2, "bench.window")))
    return _f(1, device) + _f(1, host_plane) + _f(4, "host-0")


def test_op_metadata_reads_each_kernels_op_name(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    meta = scopes.op_metadata(str(path))
    assert meta[(D0, GATE)] == {
        "tf_op": "jit(step)/jit(main)/ffn.gate/pallas_call", "flops": "3.0"}
    assert meta[(D0, UP)] == {"tf_op": "jit(step)/ffn.up/pallas_call"}
    assert (HOST, "bench.window") not in meta


def test_load_events_gives_each_kernel_its_scope(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    events = {(e.line, e.name): e for e in scopes.load_events(str(path))}
    gate, up = events[(OPS_LINE, GATE)], events[(OPS_LINE, UP)]
    assert (gate.scope, up.scope) == ("ffn.gate", "ffn.up")
    assert (gate.start_ns, gate.dur_ns) == (1100.0, 50.0)
    assert events[(MODULES_LINE, "jit_step")].scope == ""
    s = reduce_scopes(list(events.values()), kernel_names=KERNELS)
    assert s["kernel_s_by_scope"] == {"ffn.gate": pytest.approx(50e-9),
                                      "ffn.up": pytest.approx(30e-9)}
    assert s["in_call_idle_s"] == pytest.approx(50e-9)
