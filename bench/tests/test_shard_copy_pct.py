"""``metrics/shard_copy_pct.py`` against hand-worked values: a synthetic
two-device trace of the sharded path, and the traces recorded on one chip."""

from __future__ import annotations

import pytest

import run
from bench_tiny import BENCH

trace = run.load_module(BENCH / "trace.py")
shard_copy = run.load_module(BENCH / "metrics" / "shard_copy_pct.py")
exchange = run.load_module(BENCH / "metrics" / "exchange_exposed_pct.py")
DATA = BENCH / "tests" / "data"


def iv(name, start, end, kind=""):
    return trace.Interval(name, float(start), float(end), kind)


def synthetic():
    """One call in a 100 ns window on two devices.

    host: call [0, 10] sync [10, 100]
    TPU:0: collective-permute-start [0, 2], concatenate fusion [5, 20],
           collective-permute-done [18, 22], kernel [22, 70],
           slice copy [70, 85]; idle [2, 5] and [85, 100]
           busy = 2 + 80 = 82; copies 15 + 15 = 30
           collectives alone: [0, 2] + [20, 22] = 4
    TPU:1: concatenate [0, 10], kernel [10, 60], a custom call that is no
           engine kernel [60, 65], collective-permute-done [62, 70];
           idle [70, 100]
           busy = 70; copies 10 + 5 = 15; collectives alone: [65, 70] = 5
    """
    tr = trace.Trace()
    tr.spans = [iv("bench.call", 0, 10), iv("bench.sync", 10, 100)]
    tr.devices = {
        "TPU:0": [iv("collective-permute-start.1", 0, 2,
                     "collective-permute-start"),
                  iv("concatenate_fusion", 5, 20, "fusion"),
                  iv("collective-permute-done.1", 18, 22,
                     "collective-permute-done"),
                  iv("stencil_stream_jtiled.1", 22, 70, "custom-call"),
                  iv("copy.3", 70, 85, "copy")],
        "TPU:1": [iv("concatenate.4", 0, 10, "concatenate"),
                  iv("stencil_stream_jtiled.1", 10, 60, "custom-call"),
                  iv("other_call.2", 60, 65, "custom-call"),
                  iv("collective-permute-done.1", 62, 70,
                     "collective-permute-done")]}
    return tr


def test_synthetic_hand_worked():
    tr = synthetic()
    assert shard_copy.read(tr, {}) == pytest.approx(
        (100 * 30 / 82 + 100 * 15 / 70) / 2)
    assert exchange.read(tr, {}) == pytest.approx(
        (100 * 4 / 82 + 100 * 5 / 70) / 2)


@pytest.mark.parametrize("name,kind,kernel", [
    ("stencil_strip.7", "custom-call", True),
    ("stencil_stream_jtiled.1", "custom-call", True),
    ("stencil_strip.7", "fusion", False),
    ("other_call.2", "custom-call", False),
    ("_stencil_apply_impl.1", "custom-call", False),
])
def test_kernel_is_a_stencil_custom_call(name, kind, kernel):
    """An engine kernel is a custom call named ``stencil_*``; a fusion of
    that name, or another custom call, is not one."""
    assert shard_copy.is_kernel(iv(name, 0, 1, kind)) == kernel


def test_reads_nothing_without_devices():
    tr = synthetic()
    tr.devices = {}
    assert shard_copy.read(tr, {}) is None


def test_recorded_smooth4_trace_reads_about_zero():
    """One chip, no exchange: beside the named kernel only a reshape and an
    iota fusion a call, 20,224 ns of 803,033,383 busy."""
    tr = trace.load(DATA / "j27-f32.smooth4.xplane.pb.gz")
    assert shard_copy.read(tr, {}) == pytest.approx(
        100 * 20_224 / 803_033_383)
    assert shard_copy.read(tr, {}) < 0.01


def test_recorded_apply_trace_predates_the_kernel_names():
    """The apply trace was recorded before the engine's kernels carried
    their names: its kernel is the jitted function's custom call
    (``_stencil_apply_impl.1``), which the reader cannot tell from a copy,
    so everything reads as one."""
    tr = trace.load(DATA / "j27-f32.apply.xplane.pb.gz")
    (top, _), *_ = tr.top_ops(1)
    assert top == "_stencil_apply_impl.1 (custom-call)"
    assert shard_copy.read(tr, {}) == pytest.approx(100.0)


# Counted from the raw events of 0.25 s of `j27-f32-x4.apply` on a
# four-chip v5e host (ProfileData alone; the file keeps the device op
# lines and the harness's and engine's host events, the rest trimmed):
# 13 calls; per device, busy ns in the window and the union of the ops
# that are neither a `stencil_*` custom call nor a collective-permute (the
# wrapper's two `copy.1`s, `pad_maximum_fusion`, the crop `slice.12`, the
# small weight and geometry copies).
X4 = DATA / "j27-f32-x4.apply.xplane.pb.gz"
X4_BUSY_COPY = {"TPU:0": (232_866_697, 165_943_381),
                "TPU:1": (232_870_246, 165_952_460),
                "TPU:2": (232_843_708, 165_945_403),
                "TPU:3": (232_571_493, 165_953_482)}


def test_recorded_four_chip_trace_hand_worked():
    tr = trace.load(X4)
    lo, hi = tr.window()
    assert len(tr.calls()) == 13 and hi - lo == 252_283_872
    assert sorted(tr.devices) == sorted(X4_BUSY_COPY)
    for dev, (busy, copy) in X4_BUSY_COPY.items():
        assert tr.busy_ns(dev, lo, hi) == busy
        assert tr.exposed_ns(dev, lo, hi, shard_copy.is_copy)[0] == copy
    want = sum(100 * c / b for b, c in X4_BUSY_COPY.values()) / 4
    assert shard_copy.read(tr, {}) == pytest.approx(want)
    assert 71 < want < 72
    # the exchange: two 1 MiB permutes a call, almost wholly hidden
    assert 0 < exchange.read(tr, {}) < 1
