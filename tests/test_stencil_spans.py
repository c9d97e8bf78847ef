"""The engine's profiler spans, read back from a trace recorded on the CPU.

Each public entry call is one outermost entry span (``stencil.apply``,
``stencil.sweep_driver``, ``stencil.sharded``) holding the host's choice
(``stencil.select``) and the executor call (``stencil.launch``); a
``stencil.trace`` span runs only while JAX traces a jitted body, so it
counts retraces.  Pallas runs interpreted here, on tiny grids.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.kernels import (autotune_sweeps, compile_plan, stencil_apply,
                           stencil_sweep_driver)
from repro.kernels.stencil_engine import common

SRC = Path(__file__).resolve().parents[1] / "src"
W27 = np.array([[[26, -1], [-1, -1]], [[-1, -1], [-1, -1]]], np.float32)


def read_spans(trace_dir) -> list:
    """``[(name, start_ns, end_ns, args)]`` of the ``stencil.*`` spans in
    the one trace under ``trace_dir``, in order of start."""
    path, = Path(trace_dir).glob("**/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in common.SPANS:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


def record(trace_dir, *calls) -> list:
    """The spans of ``calls``, each run to completion under one trace."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        for call in calls:
            call().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    return read_spans(trace_dir)


def inside(span, outer) -> bool:
    return outer[1] <= span[1] and span[2] <= outer[2]


def outermost_entries(spans) -> list:
    entries = [s for s in spans if s[0] in common.ENTRY_SPANS]
    return [s for s in entries
            if not any(o is not s and inside(s, o) for o in entries)]


def check_nesting(spans, entry: str, calls: int) -> list:
    """One outermost ``entry`` span per call, each holding its select and
    launch spans, which do not overlap; returns the outermost spans."""
    outer = outermost_entries(spans)
    assert [s[0] for s in outer] == [entry] * calls
    for o in outer:
        mine = [s for s in spans if inside(s, o)]
        sel = [s for s in mine if s[0] == common.SPAN_SELECT]
        launch = [s for s in mine if s[0] == common.SPAN_LAUNCH]
        assert sel and launch
        assert not any(s[1] < l[2] and l[1] < s[2]
                       for s in sel for l in launch)
    for s in spans:         # nothing of the program outside a call
        assert any(inside(s, o) for o in outer), s
    return outer


def field(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).uniform(-1, 1, shape),
                       jnp.float32)


@pytest.mark.parametrize("entry,call", [
    (common.SPAN_APPLY,
     lambda u: stencil_apply(u, W27, "stencil27", bc="clamp")),
    (common.SPAN_SWEEP_DRIVER,
     lambda u: stencil_sweep_driver(u, W27, "stencil27", sweeps=4,
                                    bc="clamp")),
    # fused and chained call stencil_apply from inside the sweep driver
    (common.SPAN_SWEEP_DRIVER,
     lambda u: stencil_sweep_driver(u, W27, "stencil27", sweeps=2,
                                    mode="fused", bc="clamp")),
    (common.SPAN_SWEEP_DRIVER,
     lambda u: stencil_sweep_driver(u, W27, "stencil27", sweeps=2,
                                    mode="chained", bc="clamp")),
], ids=["apply", "driver-auto", "driver-fused", "driver-chained"])
def test_entry_spans_nest_and_trace_once(tmp_path, entry, call):
    """Two calls on a shape no other test uses: one outermost entry span
    each, select and launch inside; one retrace in the first call (each
    jitted executor it runs traces once), none in the second."""
    jax.clear_caches()
    u = field((24, 16, 128))
    spans = record(tmp_path, lambda: call(u), lambda: call(u))
    first, second = check_nesting(spans, entry, 2)
    traces = [[s for s in spans
               if s[0] == common.SPAN_TRACE and inside(s, o)]
              for o in (first, second)]
    assert len(traces[0]) == 1 and traces[1] == []
    assert {"path", "block_i", "plan", "sweeps"} <= set(traces[0][0][3])
    assert "i_halo" not in traces[0][0][3]      # one device: no exchange


def test_driver_select_carries_the_autotuned_choice(tmp_path):
    u = field((24, 16, 128))
    spans = record(tmp_path, lambda: stencil_sweep_driver(
        u, W27, "stencil27", sweeps=4, bc="clamp"))
    (driver,) = outermost_entries(spans)
    sel = [s for s in spans if s[0] == common.SPAN_SELECT
           and inside(s, driver)]
    want = autotune_sweeps(24, 16, 128, 4, 4, compile_plan("stencil27"))
    args = {k: v for k, v in
            dict(mode=want.mode, path=want.path, block_i=want.block_i,
                 block_j=want.block_j).items() if v is not None}
    assert sel[0][3] == args    # the sweep driver's own choice comes first


def test_apply_records_its_trace_time_choice(tmp_path):
    u = field((8, 16, 128))
    spans = record(tmp_path, lambda: stencil_apply(
        u, W27, "stencil27", block_i=4, path="replicate", bc="clamp"))
    (trace,) = [s for s in spans if s[0] == common.SPAN_TRACE]
    assert trace[3] == {"path": "replicate", "block_i": 4,
                        "plan": compile_plan("stencil27").kind, "sweeps": 1}


@pytest.mark.parametrize("n,bj,dtype,want", [
    (32, 16, jnp.float32, 8),       # the halo blocks engaged
    (32, 16, jnp.bfloat16, 16),     # bf16 at bj=16: whole neighbour tiles
    (24, 12, jnp.float32, 12),      # 8 does not divide 12: whole tiles
], ids=["f32", "bf16-fallback", "bj12-fallback"])
def test_jtiled_stream_trace_carries_halo_rows(tmp_path, n, bj, dtype, want):
    """``halo_rows`` is the height of the j-halo blocks staged beside each
    tile: 8 for f32 when the narrowing engages, ``bj`` on fallback."""
    jax.clear_caches()
    u = field((8, n, 128)).astype(dtype)
    spans = record(tmp_path, lambda: stencil_apply(
        u, W27, "stencil27", block_i=4, block_j=bj, path="stream",
        bc="clamp"))
    (trace,) = [s for s in spans if s[0] == common.SPAN_TRACE]
    assert trace[3]["path"] == "stream" and trace[3]["block_j"] == bj
    assert trace[3]["halo_rows"] == want


class _Refuses:
    def set_metadata(self, **_):
        raise AssertionError("span arguments set with no profiler active")


def test_span_arguments_only_while_a_profiler_records():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    common.annotate(_Refuses(), mode="fused")


SHARDED = textwrap.dedent("""
    import json, sys
    import jax, numpy as np
    sys.path.insert(0, {tests!r})
    from test_stencil_spans import W27, field, record
    from repro.kernels import stencil_sharded
    assert len(jax.devices()) == 4, jax.devices()
    mesh = jax.make_mesh((4,), ("i",))
    u = field((32, 16, 128))
    def call():
        return stencil_sharded(u, W27, "stencil27", mesh=mesh, axis="i",
                               bc="clamp")
    print(json.dumps(record({trace!r}, call, call)))
""")


def test_sharded_spans_on_four_devices(tmp_path):
    """``stencil_sharded`` on a (4,) mesh of virtual CPU devices, in a
    process of its own (this one sees one device)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(SRC),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    script = SHARDED.format(tests=str(Path(__file__).parent),
                            trace=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    spans = [tuple(s) for s in json.loads(proc.stdout.splitlines()[-1])]
    first, second = check_nesting(spans, common.SPAN_SHARDED, 2)
    sel = [s for s in spans if s[0] == common.SPAN_SELECT]
    assert {"mode", "path", "block_i"} <= set(sel[0][3])
    traces = [s for s in spans if s[0] == common.SPAN_TRACE]
    assert len(traces) == 1 and inside(traces[0], first)
    assert traces[0][3]["mode"] == sel[0][3]["mode"]
    # the chain's geometry: one i plane exchanged each side, 8 local rows
    assert sel[0][3]["halo"] == str((1, 0, 0))
    assert sel[0][3]["local"] == str((8, 16, 128))
    # halo_rows only on a j-tiled stream call
    tiled = traces[0][3]["path"] == "stream" and "block_j" in traces[0][3]
    assert ("halo_rows" in traces[0][3]) == tiled
    # the stream chain's kernel takes the exchanged i planes as operands
    assert traces[0][3]["i_halo"] == "operands"


SHARDED_CASE = textwrap.dedent("""
    import json, sys
    import jax
    sys.path.insert(0, {tests!r})
    from test_stencil_spans import W27, field, record
    from repro.kernels import stencil_sharded
    assert len(jax.devices()) == 4, jax.devices()
    mesh = jax.make_mesh({mesh_shape!r}, {mesh_axes!r})
    u = field({shape!r})
    def call():
        return stencil_sharded(u, W27, "stencil27", mesh=mesh, bc="clamp",
                               **{kwargs!r})
    print(json.dumps(record({trace!r}, call)))
""")


@pytest.mark.parametrize("mesh_shape,mesh_axes,shape,kwargs,select,trace", [
    # the i chain, j-tiled by a passed block_j: the narrowing engages
    ((4,), ("i",), (32, 32, 128), dict(axis="i", block_j=16),
     dict(halo=str((1, 0, 0)), local=str((8, 32, 128))),
     dict(block_j=16, halo_rows=8, i_halo="operands")),
    # a 2x2x1 process grid: per-axis (i, j, k) halo and local extents
    ((2, 2), ("i", "j"), (16, 16, 128), dict(axes=("i", "j", None)),
     dict(halo=str((1, 1, 0)), local=str((8, 8, 128))),
     dict(i_halo="operands")),
    # the wavefront kernel sweeps the slab the exchanged planes extend
    ((4,), ("i",), (32, 16, 128), dict(axis="i", mode="wavefront"),
     dict(halo=str((1, 0, 0)), mode="wavefront"),
     dict(mode="wavefront", i_halo="concat")),
    # i whole: no i planes exchanged, no argument
    ((4,), ("j",), (8, 32, 128), dict(axes=(None, "j", None)),
     dict(halo=str((0, 1, 0))), dict(i_halo=None)),
], ids=["chain-jtiled", "grid-2x2x1", "chain-wavefront", "j-only"])
def test_sharded_spans_carry_the_shard_geometry(tmp_path, mesh_shape,
                                                mesh_axes, shape, kwargs,
                                                select, trace):
    """The sharded entry's ``stencil.select`` carries the exchange depth
    and the local extents; the shard body's ``stencil.trace`` carries
    ``halo_rows`` on a j-tiled stream call and not otherwise, and
    ``i_halo``, how the exchanged i planes reach the kernel, where i is
    sharded.  Span arguments that are tuples are recorded as their
    text."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(SRC),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    script = SHARDED_CASE.format(tests=str(Path(__file__).parent),
                                 mesh_shape=mesh_shape, mesh_axes=mesh_axes,
                                 shape=shape, kwargs=kwargs,
                                 trace=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    spans = [tuple(s) for s in json.loads(proc.stdout.splitlines()[-1])]
    (sel,) = [s[3] for s in spans if s[0] == common.SPAN_SELECT]
    (tr,) = [s[3] for s in spans if s[0] == common.SPAN_TRACE]
    assert {k: sel.get(k) for k in select} == select
    assert {k: tr.get(k) for k in trace} == trace
    assert ("halo_rows" in tr) == ("halo_rows" in trace)
