"""``program.py`` and the readers of the program's spans, against
hand-worked values: on a small synthetic trace, and on traces recorded on a
TPU v5e (``j27-f32.apply`` before the engine had spans, ``j27-f32.smooth4``
with them)."""

from __future__ import annotations

import gzip
import json
import tempfile

import pytest

import run
from bench_tiny import BENCH

trace = run.load_module(BENCH / "trace.py")
program = run.load_module(BENCH / "program.py")
DATA = BENCH / "tests" / "data"
APPLY = DATA / "j27-f32.apply.xplane.pb.gz"
NEW = ("select_ms", "launch_ms", "idle_in_program_pct")
OLD = ("stencil_roofline", "device_idle_pct", "dispatch_ms",
       "exchange_exposed_pct")


def reader(name):
    return run.load_module(BENCH / "metrics" / f"{name}.py").read


def record():
    cfg = json.loads((BENCH / "configs" / "j27-f32.json").read_text())
    return {"config": cfg, "sweeps": 1, "device_kind": "TPU v5 lite"}


def sp(name, start, end):
    return program.Span(name, float(start), float(end))


def synthetic(empty_offset=False):
    """Two calls into the sweep driver in a 100 ns window, two devices.

    host (ns):  call [0, 30]  driver [2, 28]: select [3, 13],
                              apply [14, 27]: select [15, 17], launch [18, 26]
                sync [30, 60]
                call [62, 80] driver [63, 79]: select [64, 70], launch [71, 78]
                sync [80, 100]
    select per call 12, 6; launch per call 8, 7: medians 9 and 7.5 ns.

    TPU:0 (host = device + 10): runs [17, 45] and [62, 85], enqueued at 25
    and 71, completion seen at 57 and 96: offsets [8, 12] and [9, 11], so
    [9, 11], midpoint 10.  Idle on the host clock: [0, 27], [55, 72],
    [95, 100] (49 ns); inside a call [2, 27], [63, 72] (34).
    TPU:1 (host = device - 5): runs [33, 58] and [78, 99], enqueued at 26
    and 71, completion seen at 55 and 97: offsets [-7, -3] and [-7, -2],
    so [-7, -3], midpoint -5.  Idle: [0, 28], [53, 73], [94, 100] (54 ns);
    inside a call [2, 28], [63, 73] (36).
    """
    tr = trace.Trace()
    tr.spans = [trace.Interval("bench.call", 0.0, 30.0),
                trace.Interval("bench.sync", 30.0, 60.0),
                trace.Interval("bench.call", 62.0, 80.0),
                trace.Interval("bench.sync", 80.0, 100.0)]
    k = "stencil_stream_jtiled"
    tr.devices = {
        "TPU:0": [trace.Interval(k, 17.0, 45.0, "custom-call"),
                  trace.Interval(k, 62.0, 85.0, "custom-call")],
        "TPU:1": [trace.Interval(k, 33.0, 58.0, "custom-call"),
                  trace.Interval(k, 78.0, 99.0, "custom-call")]}
    prog = program.Program()
    prog.spans = sorted(
        [sp(s.name, s.start, s.end) for s in tr.spans]
        + [sp("stencil.sweep_driver", 2, 28), sp("stencil.select", 3, 13),
           sp("stencil.apply", 14, 27), sp("stencil.select", 15, 17),
           sp("stencil.launch", 18, 26),
           sp("stencil.sweep_driver", 63, 79), sp("stencil.select", 64, 70),
           sp("stencil.launch", 71, 78)],
        key=lambda s: (s.start, -s.end))
    prog.runs = {
        "TPU:0": [program.Run(17, 45, 25, 57), program.Run(62, 85, 71, 96)],
        "TPU:1": [program.Run(33, 58, 26, 49 if empty_offset else 55),
                  program.Run(78, 99, 71, 97)]}
    tr.program = prog
    return tr, prog


def test_alignment_hand_worked():
    tr, prog = synthetic()
    assert prog.offset_interval("TPU:0", 0, 100) == (9, 11)
    assert prog.offset("TPU:0", 0, 100) == 10
    assert prog.offset_interval("TPU:1", 0, 100) == (-7, -3)
    assert prog.offset_interval("TPU:1", 30, 100) == (-7, -2)  # one run
    assert prog.offset_interval("TPU:1", 80, 100) is None      # no run
    _, bad = synthetic(empty_offset=True)
    assert bad.offset_interval("TPU:1", 0, 100) is None        # lo > hi


def test_new_readers_hand_worked():
    tr, prog = synthetic()
    assert reader("select_ms")(tr, record()) == pytest.approx(9e-6)
    assert reader("launch_ms")(tr, record()) == pytest.approx(7.5e-6)
    assert reader("idle_in_program_pct")(tr, record()) == pytest.approx(
        (100 * 34 / 49 + 100 * 36 / 54) / 2)
    assert prog.host_spans(0, 100) == {
        "stencil.apply": [1, pytest.approx(13e-9)],
        "stencil.launch": [2, pytest.approx(15e-9)],
        "stencil.select": [3, pytest.approx(18e-9)],
        "stencil.sweep_driver": [2, pytest.approx(42e-9)]}
    assert sum(program.idle_by_host(tr, prog).values()) == pytest.approx(
        (49 + 54) / 2 / 1e9)


def test_new_readers_read_nothing_without_what_they_need():
    tr, _ = synthetic(empty_offset=True)       # TPU:1 cannot be aligned
    assert reader("idle_in_program_pct")(tr, record()) is None
    assert reader("select_ms")(tr, record()) == pytest.approx(9e-6)
    tr, prog = synthetic()                     # the program has no spans
    prog.spans = [s for s in prog.spans if s.name.startswith("bench.")]
    for name in NEW:
        assert reader(name)(tr, record()) is None
    tr, _ = synthetic()                        # no device: off the chip
    tr.devices = {}
    assert reader("idle_in_program_pct")(tr, record()) is None
    bare = trace.Trace(devices={}, spans=tr.spans)
    for name in NEW:                           # nothing found, nothing read
        assert reader(name)(bare, record()) is None


def test_nested_entries_count_once():
    _, prog = synthetic()
    assert [(s.name, s.start) for s in prog.entries(0, 100)] == [
        ("stencil.sweep_driver", 2.0), ("stencil.sweep_driver", 63.0)]
    assert prog.entries(50, 100)[0].start == 63.0


def test_idle_gaps_labelled_by_host_activity():
    """Each gap of ``trace.idle_gaps``, in its order, gains the activity
    that holds most of it once shifted onto the host clock."""
    tr, prog = synthetic()
    k = "stencil_stream_jtiled"
    assert program.idle_gaps(tr, prog, 10) == [
        [f"window start .. {k} on TPU:1 [host: stencil.select]",
         pytest.approx(33e-9)],
        [f"{k} .. {k} on TPU:1 [host: bench.sync]", pytest.approx(20e-9)],
        [f"window start .. {k} on TPU:0 [host: stencil.launch]",
         pytest.approx(17e-9)],
        [f"{k} .. {k} on TPU:0 [host: stencil.select]",
         pytest.approx(17e-9)],
        [f"{k} .. window end on TPU:0 [host: harness]",
         pytest.approx(15e-9)],
        [f"{k} .. window end on TPU:1 [host: bench.sync]",
         pytest.approx(1e-9)]]


# The recorded apply trace (recorded before the engine had spans), by hand from
# its raw events: 138 runs pair by run_id, 135 of them enqueued inside the
# window; enqueue end - module start peaks at 1,280,271 ns and completion
# start - module end bottoms at 1,673,145 ns.
APPLY_OFFSET = (1_280_271, 1_673_145)


def test_alignment_on_the_recorded_apply_trace():
    tr, prog = trace.load(APPLY), program.load(APPLY)
    lo, hi = tr.window()
    assert len(prog.runs["TPU:0"]) == 138
    assert prog.offset_interval("TPU:0", lo, hi) == APPLY_OFFSET
    off = prog.offset("TPU:0", lo, hi)
    for r in prog.runs["TPU:0"]:
        assert r.enqueued <= r.start + off and r.end + off <= r.completed


def test_recorded_apply_trace_old_readers_unchanged_new_read_nothing():
    tr = trace.load(APPLY)
    before = {n: reader(n)(tr, record()) for n in OLD}
    tops = tr.top_ops(10)
    tr.program = program.load(APPLY)
    for name in NEW:
        assert reader(name)(tr, record()) is None
    assert {n: reader(n)(tr, record()) for n in OLD} == before
    assert tr.top_ops(10) == tops


def test_recorded_apply_trace_gap_labels():
    tr, prog = trace.load(APPLY), program.load(APPLY)
    labelled = program.idle_gaps(tr, prog, 10)
    assert [[f"{label} [host: bench.check]" if i == 0 else
             f"{label} [host: bench.sync]", secs]
            for i, (label, secs) in enumerate(tr.idle_gaps(10))] == labelled


def test_of_finds_the_trace_file_run_writes(tmp_path, monkeypatch, capsys):
    """A harness trace is matched to its file in run.py's temporary trace
    directory by its bench.call spans, and the offset is printed once."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    kept = tmp_path / "bench-trace-x" / "plugins" / "profile" / "t"
    kept.mkdir(parents=True)
    (kept / "host.xplane.pb").write_bytes(gzip.decompress(APPLY.read_bytes()))
    cut = tmp_path / "bench-trace-y" / "plugins" / "profile" / "t"
    cut.mkdir(parents=True)                 # a newer run's, cut off
    (cut / "host.xplane.pb").write_bytes(b"not a trace")
    tr = trace.load(APPLY)
    prog = program.of(tr)
    assert prog is not None and len(prog.runs["TPU:0"]) == 138
    assert program.of(tr) is prog
    out = capsys.readouterr().out
    assert out.count("info: host-minus-device clock offset on TPU:0: "
                     "[1.280271, 1.673145] ms") == 1
    other, _ = synthetic()
    del other.program                       # calls that match no file
    assert program.of(other) is None


# The recorded smooth4 trace (a 1 s window of this program on a v5e), by
# hand from its raw events with numpy masks at 50 ns, independent of
# program.py: 47 calls, each one outermost stencil.sweep_driver span whose
# own select carries the choice fused, stream, (16, 16); 50 runs pair by
# run_id, 47 enqueued inside the window, bounding the offset to
# [1,199,231, 1,576,589] ns; no stencil.trace span (a warm window); the
# per-call select and launch medians 1.30183 and 0.21068 ms; 33.7966% of
# the aligned idle time inside a call (a 115 ms stall sits in bench.sync).
SMOOTH4 = DATA / "j27-f32.smooth4.xplane.pb.gz"
SMOOTH4_OFFSET = (1_199_231, 1_576_589)


def test_recorded_smooth4_trace_hand_worked():
    tr, prog = trace.load(SMOOTH4), program.load(SMOOTH4)
    lo, hi = tr.window()
    assert len(tr.calls()) == 47 and len(prog.entries(lo, hi)) == 47
    assert {s.name for s in prog.entries(lo, hi)} == {"stencil.sweep_driver"}
    assert prog.offset_interval("TPU:0", lo, hi) == SMOOTH4_OFFSET
    off = prog.offset("TPU:0", lo, hi)
    for r in prog.runs["TPU:0"]:
        assert r.enqueued <= r.start + off and r.end + off <= r.completed
    spans = prog.host_spans(lo, hi)
    assert "stencil.trace" not in spans
    assert {k: v[0] for k, v in spans.items()} == {
        "stencil.apply": 47, "stencil.launch": 47, "stencil.select": 94,
        "stencil.sweep_driver": 47}
    choices = {s.args for s in prog.spans if s.name == "stencil.select"}
    assert choices == {(), (("block_i", 16), ("block_j", 16),
                            ("mode", "fused"), ("path", "stream"))}
    (top, _), *_ = tr.top_ops(10)
    assert top == "stencil_stream_jtiled.1 (custom-call)"


def test_recorded_smooth4_trace_readers_hand_worked():
    tr = trace.load(SMOOTH4)
    tr.program = program.load(SMOOTH4)
    assert reader("select_ms")(tr, record()) == pytest.approx(1.30183)
    assert reader("launch_ms")(tr, record()) == pytest.approx(0.21068)
    assert reader("idle_in_program_pct")(tr, record()) == pytest.approx(
        33.7966, abs=0.01)
    labels = [g[0] for g in program.idle_gaps(tr, tr.program, 10)]
    assert labels[0].endswith("[host: bench.sync]")
    assert all(g.endswith("[host: stencil.select]") for g in labels[1:])
