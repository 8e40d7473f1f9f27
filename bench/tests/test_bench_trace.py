"""The trace reduction on a small synthetic trace."""
import pytest

import bench_tiny  # noqa: F401
from bench.trace import (MODULES_LINE, OPS_LINE, Event, parse_op,
                         reduce_trace)

D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
KERNELS = ["_stream_spmm", "_stream_panel_spmm"]


def op(plane, name, start, dur):
    return Event(plane, OPS_LINE, name, float(start), float(dur))


def host(name, start, dur):
    return Event(HOST, "python", name, float(start), float(dur))


def synthetic():
    # window 0..1000 ns; device 0 busy 100..300 (kernel + overlapping glue),
    # 500..600 (collective), 900..1100 (clipped to 1000)
    return [
        host("bench.window", 0, 1000),
        host("bench.issue", 300, 150),
        host("bench.wait", 450, 50),
        host("bench.select", 600, 300),
        op(D0, "%_stream_spmm.1 = f32[112,128,128]{2,1,0:T(8,128)S(1)} "
               "custom-call(s32[896]{0:T(1024)S(1)} %copy-done.4)", 100, 150),
        # a fusion that reads the kernel's output is glue, not kernel
        op(D0, "%fusion.2 = f32[112,128,128]{2,1,0:T(8,128)S(1)} fusion("
               "f32[112,128,128]{2,1,0} %_stream_spmm.1), kind=kCustom",
           200, 100),
        op(D0, "%all-gather.3 = f32[32,8192]{1,0} all-gather(f32[32,2048]"
               "{1,0} %fusion.2)", 500, 100),
        op(D0, "%copy-start.4 = (s32[896]{0:T(1024)S(1)}, u32[]{:S(2)}) "
               "copy-start(s32[896]{0:T(1024)} %p.1)", 900, 200),
        op(D0, "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p)", 2000, 50),
        op(D1, "%_stream_panel_spmm.7 = f32[64,128,512]{2,1,0} custom-call("
               "s32[9]{0} %p.2)", 0, 500),
        Event(D0, MODULES_LINE, "jit_step", 100.0, 200.0),
        Event(D0, MODULES_LINE, "jit_step", 500.0, 600.0),   # ends late
        Event(D0, "Steps", "ignored", 0.0, 1000.0),
    ]


def test_busy_union_idle_share_and_category_sums():
    t = reduce_trace(synthetic(), kernel_names=KERNELS)
    assert t["window_s"] == pytest.approx(1e-6)
    assert t["devices"] == 2
    # device 0: 200 + 100 + 100 = 400 ns busy; device 1: 500 ns
    assert t["busy_s"] == pytest.approx(450e-9)
    assert t["idle_share"] == pytest.approx(0.55)
    assert t["kernel_s"] == pytest.approx(650e-9)      # 150 + 500
    assert t["kernel_events"] == 2
    assert t["collective_s"] == pytest.approx(100e-9)
    assert t["glue_s"] == pytest.approx(200e-9)        # 100 + 100 clipped
    assert t["module_ms"] == pytest.approx([200e-6])


def test_idle_gaps_are_labelled_by_the_host_annotation():
    t = reduce_trace(synthetic(), kernel_names=KERNELS)
    gaps = {round(s * 1e9): label for label, s in t["idle_gaps"]}
    # gaps on device 0: 0..100, 300..500, 600..900
    assert gaps == {100: "other", 200: "issue", 300: "select"}
    assert [g[1] for g in t["idle_gaps"]] == sorted(
        (g[1] for g in t["idle_gaps"]), reverse=True)


def test_device_ops_breakdown_is_sorted_and_capped():
    t = reduce_trace(synthetic(), kernel_names=KERNELS, top=2)
    assert len(t["device_ops"]) == 2
    assert t["device_ops"][0][0] == \
        "_stream_panel_spmm.7 custom-call f32[64,128,512]"
    assert t["device_ops"][1][0] == "_stream_spmm.1 custom-call " \
        "f32[112,128,128]"


def test_parse_op_reads_name_opcode_and_shape():
    assert parse_op("%copy-start.4 = (s32[896]{0:T(1024)S(1)}, u32[]"
                    "{:S(2)}) copy-start(s32[896]{0} %p)") == (
        "copy-start.4", "copy-start", "(s32[896]{0:T(1024)S(1)}, u32[]"
        "{:S(2)})")
    assert parse_op("plain") == ("plain", "", "")


def test_a_trace_without_the_window_or_device_ops_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        reduce_trace([op(D0, "x", 0, 1)], kernel_names=KERNELS)
    with pytest.raises(ValueError, match="no device operations"):
        reduce_trace([host("bench.window", 0, 10)], kernel_names=KERNELS)
