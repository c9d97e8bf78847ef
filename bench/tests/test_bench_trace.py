"""``trace.py`` and each per-layer reader, against hand-worked values: on a
small synthetic trace, and on a trace recorded on a TPU v5e by one short
``--trace 1`` run of ``j27-f32.apply``."""

from __future__ import annotations

import json

import pytest

import run
from bench_tiny import BENCH

trace = run.load_module(BENCH / "trace.py")
RECORDED = BENCH / "tests" / "data" / "j27-f32.apply.xplane.pb.gz"


def reader(name):
    return run.load_module(BENCH / "metrics" / f"{name}.py").read


def iv(name, start, end, kind=""):
    return trace.Interval(name, float(start), float(end), kind)


def synthetic():
    """Two calls in a 100 ns window on two devices.

    host: call [0, 12] sync [12, 50]; call [50, 54] sync [54, 100]
    TPU:0: fusion [12, 40], kernel [30, 48], kernel [60, 95]
           busy = [12, 48] + [60, 95] = 36 + 35 = 71
    TPU:1: kernel [20, 60], collective-permute-done [55, 70],
           all-reduce [90, 99]
           busy = [20, 70] + [90, 99] = 50 + 9 = 59
           collectives alone: [60, 70] + [90, 99] = 19
    An op outside the window is clipped away.
    """
    tr = trace.Trace()
    tr.spans = [iv("bench.call", 0, 12), iv("bench.sync", 12, 50),
                iv("bench.call", 50, 54), iv("bench.sync", 54, 100),
                iv("bench.check", 120, 200)]
    tr.devices = {
        "TPU:0": [iv("fusion.1", 12, 40, "fusion"),
                  iv("stencil_kernel", 30, 48, "custom-call"),
                  iv("stencil_kernel", 60, 95, "custom-call"),
                  iv("reference", 130, 190, "fusion")],
        "TPU:1": [iv("stencil_kernel", 20, 60, "custom-call"),
                  iv("collective-permute-done.2", 55, 70,
                     "collective-permute-done"),
                  iv("all-reduce.3", 90, 99, "all-reduce")]}
    return tr


def test_intervals_hand_worked():
    tr = synthetic()
    assert tr.window() == (0.0, 100.0)
    assert tr.busy_ns("TPU:0", 0, 100) == 71.0
    assert tr.busy_ns("TPU:1", 0, 100) == 59.0
    assert trace.complement([(12, 48), (60, 95)], 0, 100) == [
        (0, 12), (48, 60), (95, 100)]
    assert trace.subtract([(0, 10), (20, 30)], [(5, 22), (25, 26)]) == [
        (0, 5), (22, 25), (26, 30)]


def test_readers_hand_worked():
    tr = synthetic()
    cfg = json.loads((BENCH / "configs" / "j27-f32.json").read_text())
    record = {"config": cfg, "sweeps": 1, "device_kind": "TPU v5 lite"}
    least = 2**31 / 819e9                        # bytes bound, seconds
    want = (100 * least * 2 / 71e-9 + 100 * least * 2 / 59e-9) / 2
    assert reader("stencil_roofline")(tr, record) == pytest.approx(want)
    assert reader("device_idle_pct")(tr, record) == pytest.approx(
        (29 + 41) / 2)
    assert reader("dispatch_ms")(tr, record) == pytest.approx(8e-6)
    assert reader("exchange_exposed_pct")(tr, record) == pytest.approx(
        (0 + 100 * 19 / 59) / 2)


def test_breakdown_hand_worked():
    tr = synthetic()
    ops = dict(tr.top_ops(10))
    assert ops["stencil_kernel (custom-call)"] == pytest.approx(
        (18 + 35 + 40) / 2 / 1e9)
    assert ops["fusion.1 (fusion)"] == pytest.approx(28 / 2 / 1e9)


def test_idle_gaps_named_by_the_ops_that_bound_them():
    """Each gap of the synthetic trace, longest first, named by the op that
    ends before it and the op that starts after it."""
    assert synthetic().idle_gaps(10) == [
        ["window start .. stencil_kernel on TPU:1", pytest.approx(20e-9)],
        ["collective-permute-done.2 .. all-reduce.3 on TPU:1",
         pytest.approx(20e-9)],
        ["window start .. fusion.1 on TPU:0", pytest.approx(12e-9)],
        ["stencil_kernel .. stencil_kernel on TPU:0", pytest.approx(12e-9)],
        ["stencil_kernel .. window end on TPU:0", pytest.approx(5e-9)],
        ["all-reduce.3 .. window end on TPU:1", pytest.approx(1e-9)]]
    assert synthetic().idle_gaps(2) == synthetic().idle_gaps(10)[:2]


def test_op_names_and_opcodes():
    op = trace.op_interval(
        "%collective-permute-start.1 = (f32[1,1,512,512]{3,2,1,0:T(8,128)}, "
        "u32[]{:S(2)}) collective-permute-start(f32[1,1,512,512]{3,2,1,0} "
        "%slice.4), channel_id=1", 0, 1)
    assert (op.name, op.kind) == ("collective-permute-start.1",
                                  "collective-permute-start")
    op = trace.op_interval(
        "%fusion.7 = f32[8]{0:T(128)S(1)} fusion(f32[8]{0} "
        "%collective-permute-done.1), kind=kLoop", 0, 1)
    assert (op.name, op.kind) == ("fusion.7", "fusion")
    assert trace.op_interval("plain", 0, 1).kind == ""


def test_readers_find_nothing_without_devices():
    tr = synthetic()
    tr.devices = {}
    record = {"config": {}, "sweeps": 1, "device_kind": "cpu"}
    for name in ("stencil_roofline", "device_idle_pct",
                 "exchange_exposed_pct"):
        assert reader(name)(tr, record) is None
    one = synthetic()
    del one.devices["TPU:1"]
    assert reader("exchange_exposed_pct")(one, record) is None


# Worked by hand from the recorded trace's raw events (ProfileData alone):
# 135 bench.call/bench.sync pairs; the window runs 1,001,137,716 ns from the
# first call to the last sync's end; in it TPU:0 ran 403 ops, none
# overlapping: 135 stencil kernels (890,208,555 ns in all, the first cut at
# the window's start), 134 reshapes and 134 iota fusions (58,524 ns
# together; the first call's two fall before the window's start, on the
# device's clock); the median call returned after 212,469 ns.
CALLS, OPS, WINDOW_NS, BUSY_NS = 135, 403, 1_001_137_716, 890_267_079
KERNEL_NS, DISPATCH_NS = 890_208_555, 212_469


def test_recorded_trace_hand_worked():
    tr = trace.load(RECORDED)
    assert list(tr.devices) == ["TPU:0"]
    lo, hi = tr.window()
    assert len(tr.calls()) == CALLS and hi - lo == WINDOW_NS
    assert len(tr.ops("TPU:0", lo, hi)) == OPS
    assert tr.busy_ns("TPU:0", lo, hi) == pytest.approx(BUSY_NS, abs=1)
    (top, secs), *_ = tr.top_ops(10)
    assert top == "_stencil_apply_impl.1 (custom-call)"
    assert secs == pytest.approx(KERNEL_NS / 1e9)
    assert len(tr.idle_gaps(200)) >= CALLS - 1


def test_recorded_trace_readers_hand_worked():
    tr = trace.load(RECORDED)
    cfg = json.loads((BENCH / "configs" / "j27-f32.json").read_text())
    record = {"config": cfg, "sweeps": 1, "device_kind": "TPU v5 lite"}
    least = 2**31 / 819e9
    assert reader("stencil_roofline")(tr, record) == pytest.approx(
        100 * least * CALLS / (BUSY_NS / 1e9))
    assert reader("device_idle_pct")(tr, record) == pytest.approx(
        100 * (1 - BUSY_NS / WINDOW_NS))
    assert reader("dispatch_ms")(tr, record) == pytest.approx(
        DISPATCH_NS / 1e6)
    assert reader("exchange_exposed_pct")(tr, record) is None
