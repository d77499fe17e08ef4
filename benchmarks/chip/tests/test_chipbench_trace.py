"""The reduction from a profiler trace to busy time, kernel time and
attributed idle gaps: on a hand-made trace with known answers, and on a
small trace recorded on a TPU v5e (``data/trace_small.json``: one engine
step of danube-chat, cut from a ``--trace 1`` run)."""
import json

import pytest

from chipbench import devtrace, harness, layer, work

DATA = harness.HERE / "tests" / "data" / "trace_small.json"

GEMV = ("%lutq_gemv_packed.7 = f32[32,2560]{1,0:T(8,128)} custom-call("
        "bf16[32,2560]{1,0} %fusion.3, u8[1280,2560]{1,0} %p)")
ATTN = ("%closed_call.13 = bf16[32,8,4,80]{3,2,1,0} custom-call("
        "s32[32,40]{1,0} %get-tuple-element.9, s32[32]{0} %b)")
LOOP = "%while.5 = (s32[], bf16[32,1,2560]{2,0,1}) while((s32[]) %t)"
HAND = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_step", 0, 1000]]},
        {"name": "XLA Ops", "events": [
            [GEMV, 100, 200],                                   # 100-300
            ["%fusion.1 = bf16[32,2560]{1,0} fusion(%a)", 250, 100],
            [ATTN, 500, 100],                                   # 500-600
            [GEMV, 900, 50],                                    # 900-950
        ]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [
            ["traced_window", 0, 1000],
            ["engine_step", 50, 400],
            ["token_sync", 600, 300],
            ["submit", 610, 10],
        ]}]},
]}
DANUBE = {"n_heads": 32, "n_kv_heads": 8, "head_dim": 80}


def test_busy_union_and_gaps():
    ops = devtrace.device_ops(HAND)["/device:TPU:0"]
    assert devtrace.busy_ns(ops, 0, 1000) == 200 + 50 + 100 + 50
    assert devtrace.gaps(ops, 0, 1000) == [(0, 100), (350, 500), (600, 900),
                                           (950, 1000)]
    # clipping to a sub-window
    assert devtrace.busy_ns(ops, 200, 550) == 150 + 50


def test_kernel_time_by_name_and_result():
    ops = devtrace.device_ops(HAND)["/device:TPU:0"]
    assert devtrace.op_seconds(ops, work.kernel_match("lutq_dot")) == \
        pytest.approx(250e-9)
    attn = work.kernel_match("paged_attn", DANUBE, 32)
    assert devtrace.op_seconds(ops, attn) == pytest.approx(100e-9)
    # another batch size is another kernel call shape
    assert devtrace.op_seconds(
        ops, work.kernel_match("paged_attn", DANUBE, 16)) == 0


def test_labels_and_loops():
    assert devtrace.label(GEMV) == \
        "lutq_gemv_packed.7 f32[32,2560] custom-call"
    assert devtrace.label(ATTN) == "closed_call.13 bf16[32,8,4,80] custom-call"
    assert devtrace.is_container(LOOP) and not devtrace.is_container(GEMV)
    ops = [(LOOP, 0, 1000), (GEMV, 10, 20), (GEMV, 40, 20)]
    assert devtrace.top_ops(ops) == [
        ["lutq_gemv_packed.7 f32[32,2560] custom-call", pytest.approx(40e-9)]]


def test_reduce_and_breakdown():
    red = devtrace.reduce(HAND, 0, 1000, ("traced_window", "engine_step",
                                          "token_sync", "submit"))
    assert red["busy_s"] == pytest.approx(400e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    top = red["breakdown"]["device_ops"]
    assert top[0] == ["lutq_gemv_packed.7 f32[32,2560] custom-call",
                      pytest.approx(250e-9)]
    gaps = red["breakdown"]["idle_gaps"]
    # longest first; each named by the innermost host span at its middle
    assert gaps[0] == ["token_sync", pytest.approx(300e-9)]
    assert gaps[1] == ["engine_step", pytest.approx(150e-9)]
    assert len(gaps) == 4
    assert layer.idle_share({"trace": red}) == pytest.approx(60.0)


def test_no_device_plane_is_an_error():
    with pytest.raises(RuntimeError):
        devtrace.reduce({"planes": HAND["planes"][1:]}, 0, 1, ())


def test_recorded_trace():
    """One decode step of danube-chat at 32 slots, recorded on a v5e: the
    reduction reads the step's device time and its kernels' shares."""
    tr = json.loads(DATA.read_text())
    spans = devtrace.host_spans(tr, ("traced_window",))
    assert len(spans) == 1
    _, lo, dur = spans[0]
    red = devtrace.reduce(tr, lo, lo + dur, ("traced_window", "engine_step",
                                             "token_sync", "submit",
                                             "generator_wait"))
    assert red["window_s"] == pytest.approx(0.123040937)
    assert red["busy_s"] == pytest.approx(0.120605332)
    ops = red["ops"]
    assert devtrace.op_seconds(ops, work.kernel_match("lutq_dot")) == \
        pytest.approx(0.056942018)
    assert devtrace.op_seconds(
        ops, work.kernel_match("paged_attn", DANUBE, 32)) == \
        pytest.approx(0.012892272)
    top = red["breakdown"]["device_ops"]
    assert len(top) == 10
    assert top[0][0] == "lutq_gemv_packed.71 f32[32,2560] custom-call"
    assert not any(devtrace.is_container(n) for n, _ in top)
    assert red["breakdown"]["idle_gaps"][0][0] == "token_sync"
