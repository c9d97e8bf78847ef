"""Figures 8-10 analogue: measured stencil throughput over problem sizes.

On this CPU container we measure the jitted XLA stencil (the ref oracle) --
wall-clock Mstencil/s across the cache hierarchy, the same experiment shape
as the paper's Figures 8-10 -- and verify the Pallas kernel (interpret mode)
against it at each size.  TPU numbers come from running the same harness on
real hardware.

The tail rows exercise the unified stencil engine: batched execution, fused
multi-sweep Jacobi (``s`` operator applications per HBM round-trip), a
direct-vs-cse-vs-factored plan comparison (the paper's synthesized schedule
vs the naive one, with each plan's static shift/flop counts and pass list),
a streamed-vs-replicated path comparison (the paper's plane-streaming
kernel vs the halo-replicated one, with each path's modeled bytes/point and
achieved HBM bandwidth), the radius-2 builtins (star13 / box125: streaming
still ~2 x itemsize/point where the replicated path pays 6 x), a j-tiled
run at a size where the untiled N x P slab exceeds the VMEM budget
(previously a hard wall), a 2-device halo-exchange ``shard_map`` run
(forced host-platform devices, in a subprocess pinned to the CPU so this
process keeps its single-device view), and an 8-device 2x2x2 process-grid
pair timing the serialized vs compute/communication-overlap schedules.

Besides the ``name,us_per_call,derived`` text rows, every measurement is
recorded as a dict and the whole run is dumped to ``BENCH_stencil.json``
(path overridable via ``$BENCH_STENCIL_JSON``; schema v7: per-spec plan op
counts with ``radius`` + ``pass_list`` columns, per-path modeled
bytes/point at radius 1 and 2, a per-spec ``selection`` section recording
the cost-driven compiler's chosen ``(pass_list, unroll)``, its modeled
cycles/point, and the losing candidates -- including a
variable-coefficient variant -- a ``sweeps`` section recording the
sweeps-aware autotuner's (fused / wavefront / chained) verdict per
``(spec, s)`` with each mode's modeled bytes/point and time, a ``guard``
section recording the default :class:`GuardPolicy`'s modeled check traffic
as a fraction of the streaming path, and a ``sharded`` section recording
the multi-axis grid's modeled per-axis halo-exchange bytes/point at the
``GRID_REF`` geometry) -- which CI uploads as an artifact.

``python benchmarks/stencil_throughput.py --quick`` runs only the
streamed-vs-replicated rows plus the cost-model gates (exit 1 if the
streamed path's modeled bytes/point exceeds 2.5 x itemsize -- at radius 1
*and* radius 2 -- or regresses above the replicated path, for the
reference 27-point and star13 configurations; if the temporal
wavefront's modeled bytes/point exceeds ``1.25 * 2 * itemsize / s`` for
stencil27 at s=4; or if the default guard policy's modeled check traffic
reaches 10% of the streaming path's bytes/point) -- the fast CI guard.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.perfmodel import streaming_roofline
from repro.kernels import (GuardPolicy, autotune_engine, autotune_sweeps,
                           bytes_per_point, compile_plan,
                           exchange_bytes_per_point,
                           guard_bytes_per_point, stencil_apply,
                           stencil_ref, stencil_sweep_driver, stencil3_ref,
                           stencil7_ref, stencil27, stencil27_ref)
from repro.kernels.stencil_engine.autotune import HBM_BW, VPU_FLOPS

# The guard-overhead gate's canonical geometry: a production-depth i axis
# (the sampled checks amortize over M; REF_CONFIG's m=16 is a kernel-stress
# shape, not a serving one).
GUARD_GATE_M = 128

SIZES = (14, 30, 62, 126)

_RECORDS: List[Dict] = []


def _row(name: str, usec: float, derived: str, **fields) -> str:
    """Format one text row and mirror it into the JSON record list."""
    _RECORDS.append({"name": name, "us_per_call": round(usec, 1), **fields})
    return f"{name},{usec:.1f},{derived}"


def _time(fn, *args, reps: int = 5) -> float:
    fn(*args).block_until_ready()          # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


SELECTION_SPECS = ("stencil3", "stencil7", "stencil27", "star13", "box125",
                   "stencil27_var")

# The ``sharded`` section's reference grid: a 64^3 f32 domain on a 2x2x2
# process grid at s=2 (radius-1 deep halo = 2 planes/face).  The modeled
# per-axis exchange bytes/point are deterministic, so the regression gate
# holds them like the path/plan numbers.
GRID_REF = dict(shape=(64, 64, 64), grid=(2, 2, 2), halo=2, itemsize=4,
                sweeps=2)

# (spec, s) configurations recorded in the ``sweeps`` section: the
# sweeps-aware autotuner's (fused / wavefront / chained) race at the
# reference shape, including a radius-2 and a red-black entrant.
SWEEPS_CONFIGS = (("stencil27", 2), ("stencil27", 4), ("star13", 4),
                  ("stencil27_redblack", 2))


def _sweeps_doc(name: str, s: int) -> Dict:
    """The sweeps-aware autotuner's verdict for ``(name, s)`` at the
    reference shape: chosen mode/path/blocks, its modeled bytes/point and
    time/point per sweep, and the full candidate table it beat."""
    m, n, p, itemsize = (REF_CONFIG[k] for k in ("m", "n", "p", "itemsize"))
    sel = autotune_sweeps(m, n, p, itemsize, s, compile_plan(name))
    return sel.describe()["selection"]


def _selection_doc(name: str) -> Dict:
    """The cost-driven compiler's choice for one spec (``_var`` suffix:
    the variable-coefficient spelling): chosen kind + pass list + unroll,
    its modeled cycles/point (and which core model produced it), and the
    full candidate table it beat."""
    from repro.kernels import get_stencil
    spec = get_stencil(name[:-len("_var")]).with_coef("var") \
        if name.endswith("_var") else get_stencil(name)
    cplan = compile_plan(spec)
    d = cplan.describe()
    return {"kind": cplan.kind, "unroll": cplan.unroll,
            "pass_list": d["pass_list"], "coef": cplan.spec.coef,
            "cycles_per_point": d["selection"]["cycles_per_point"],
            "source": d["selection"]["source"],
            "candidates": d["selection"]["candidates"]}


def write_json(path: Optional[str] = None,
               default: str = "BENCH_stencil.json") -> str:
    """Dump the recorded rows + per-spec plan op counts (with ``radius``,
    ``pass_list``, and ``bc`` columns) + per-path modeled bytes/point at
    radius 1 and 2 + the per-spec cost-driven ``selection`` table to
    ``path``.  ``default`` is the fallback when neither ``path`` nor
    ``$BENCH_STENCIL_JSON`` is set: the full run refreshes the committed
    ``BENCH_stencil.json`` regression baseline; the quick gate writes the
    gitignored ``BENCH_stencil.quick.json`` so a local ``--quick`` can't
    silently clobber the baseline with a partial record set."""
    path = path or os.environ.get("BENCH_STENCIL_JSON", default)
    import dataclasses as _dc
    itemsize = REF_CONFIG["itemsize"]
    g_bpp = guard_bytes_per_point(GuardPolicy(), itemsize, GUARD_GATE_M)
    g = GRID_REF
    locs = tuple(s // n for s, n in zip(g["shape"], g["grid"]))
    doc = {
        "schema": "bench_stencil/v7",
        "guard": {
            "default_policy": _dc.asdict(GuardPolicy()),
            "gate_m": GUARD_GATE_M,
            "bytes_per_point_f32": g_bpp,
            "fraction_of_stream": g_bpp / (2.0 * itemsize),
        },
        "plans": {name: {kind: compile_plan(name, kind).describe()
                         for kind in ("direct", "cse", "factored")}
                  for name in ("stencil27", "star13", "box125")},
        "selection": {name: _selection_doc(name)
                      for name in SELECTION_SPECS},
        "sweeps": {f"{name}/s{s}": _sweeps_doc(name, s)
                   for name, s in SWEEPS_CONFIGS},
        "sharded": {
            # schema v7: the multi-axis halo-exchange traffic model at the
            # GRID_REF geometry -- the j faces ship bare, the k faces carry
            # the j ghosts, the i faces carry both (the transitive
            # j -> k -> i exchange), so per-axis bytes/point is the number
            # the overlap scheduler has to hide for i and *cannot* hide for
            # j/k.  Deterministic, gated by check_regression like the
            # per-path bytes/point.
            "grid_ref": dict(g),
            "exchange_bytes_per_point": exchange_bytes_per_point(
                g["itemsize"], (g["halo"],) * 3, locs, sweeps=g["sweeps"]),
        },
        "paths": {p: {"bytes_per_point_f32": bytes_per_point(p, 4),
                      "bytes_per_point_f32_jtiled":
                          bytes_per_point(p, 4, j_tiled=True),
                      "bytes_per_point_f32_r2":
                          bytes_per_point(p, 4, radius=2),
                      "bytes_per_point_f32_r2_jtiled":
                          bytes_per_point(p, 4, j_tiled=True, radius=2)}
                  for p in ("stream", "replicate")},
        "rows": _RECORDS,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def run() -> List[str]:
    _RECORDS.clear()
    rows = []
    rng = np.random.default_rng(0)
    j27 = jax.jit(stencil27_ref)
    j7 = jax.jit(stencil7_ref)
    j3 = jax.jit(stencil3_ref)
    for n in SIZES:
        a = jnp.asarray(rng.standard_normal((n, n, n)), jnp.float32)
        w27 = jnp.asarray(rng.uniform(0.1, 1, (2, 2, 2)), jnp.float32)
        w7 = jnp.asarray(rng.uniform(0.1, 1, 4), jnp.float32)
        w3 = jnp.asarray(rng.uniform(0.1, 1, 2), jnp.float32)
        st = (n - 2) ** 3
        t = _time(j27, a, w27)
        rows.append(_row(f"stencil27.{n}^3", t * 1e6,
                         f"{st/t/1e6:.1f} Mstencil/s",
                         mstencil_per_s=st / t / 1e6))
        t = _time(j7, a, w7)
        rows.append(_row(f"stencil7.{n}^3", t * 1e6,
                         f"{st/t/1e6:.1f} Mstencil/s",
                         mstencil_per_s=st / t / 1e6))
        a2 = a.reshape(n * n, n)
        t = _time(j3, a2, w3)
        st3 = n * n * (n - 2)
        rows.append(_row(f"stencil3.{n}^3", t * 1e6,
                         f"{st3/t/1e6:.1f} Mstencil/s",
                         mstencil_per_s=st3 / t / 1e6))
    # Pallas kernel correctness at a bench size (interpret mode)
    n = 30
    a = jnp.asarray(rng.standard_normal((n + 2, n + 2, 128)), jnp.float32)
    w27 = jnp.asarray(rng.uniform(0.1, 1, (2, 2, 2)), jnp.float32)
    got = stencil27(a, w27, block_i=4)
    ref = stencil27_ref(a, w27)
    err = float(jnp.max(jnp.abs(got - ref)))
    rows.append(_row("stencil27.pallas_vs_ref", 0.0,
                     f"max_err={err:.2e} ok={err < 1e-4}",
                     max_err=err, ok=bool(err < 1e-4)))
    # beyond-paper MXU form: correctness + napkin speedup on the TPU target
    from repro.kernels import stencil27_mxu
    got_mxu = stencil27_mxu(a, w27, block_i=4)
    err_mxu = float(jnp.max(jnp.abs(got_mxu - ref)))
    p = a.shape[-1]
    vpu_t = 54.0 / 3e12              # ~54 VPU flops/pt at ~3 TFLOP/s
    mxu_t = 8.0 * p / 197e12 + 5.0 / 3e12   # 8P MXU flops + 5 VPU adds
    rows.append(_row("stencil27.mxu_vs_ref", 0.0,
                     f"max_err={err_mxu:.2e} ok={err_mxu < 1e-4} "
                     f"napkin_speedup_v5e={vpu_t/mxu_t:.1f}x (P={p})",
                     max_err=err_mxu, ok=bool(err_mxu < 1e-4),
                     napkin_speedup_v5e=vpu_t / mxu_t))
    rows.extend(_engine_rows(rng))
    rows.extend(_plan_rows(rng))
    rows.extend(_path_rows(rng))
    rows.extend(_sweeps_rows(rng))
    rows.extend(_radius_rows(rng))
    rows.extend(_bc_rows(rng))
    rows.append(_jtiled_row(rng))
    rows.append(_guard_row(rng))
    rows.extend(check_guard_model())
    rows.append(_sharded_row())
    rows.extend(_sharded_grid_rows())
    write_json()
    return rows


def run_quick() -> List[str]:
    """CI guard: only the streamed-vs-replicated rows + the cost-model and
    wavefront gates (no size sweep, no subprocess sharding)."""
    _RECORDS.clear()
    rng = np.random.default_rng(0)
    rows = _path_rows(rng)
    rows.extend(check_stream_model())
    rows.extend(check_wavefront_model())
    rows.extend(check_guard_model())
    write_json(default="BENCH_stencil.quick.json")
    return rows


def _engine_rows(rng) -> List[str]:
    """Engine-backed scenarios: batched and fused-sweep."""
    rows: List[str] = []
    b, m, n, p = 4, 16, 24, 128
    w = jnp.asarray(rng.uniform(0.1, 1, (2, 2, 2)), jnp.float32)
    a4 = jnp.asarray(rng.standard_normal((b, m, n, p)), jnp.float32)
    st = b * (m - 2) * (n - 2) * (p - 2)

    t = _time(lambda x: stencil_apply(x, w, "stencil27", block_i=4), a4)
    err = float(jnp.max(jnp.abs(stencil_apply(a4, w, "stencil27", block_i=4)
                                - stencil_ref(a4, w, "stencil27"))))
    rows.append(_row(f"engine27.batched.{b}x{m}x{n}x{p}", t * 1e6,
                     f"{st/t/1e6:.2f} Mstencil/s max_err={err:.2e} "
                     f"ok={err < 1e-4}",
                     mstencil_per_s=st / t / 1e6, max_err=err,
                     ok=bool(err < 1e-4)))

    a3 = a4[0]
    st1 = (m - 2) * (n - 2) * (p - 2)
    for s in (1, 2, 3):
        t = _time(lambda x, s=s: stencil_apply(x, w, "stencil27", block_i=4,
                                               sweeps=s), a3)
        err = float(jnp.max(jnp.abs(
            stencil_apply(a3, w, "stencil27", block_i=4, sweeps=s)
            - stencil_ref(a3, w, "stencil27", sweeps=s))))
        rows.append(_row(f"engine27.fused_s{s}.{m}^3-ish", t * 1e6,
                         f"{s*st1/t/1e6:.2f} Mstencil/s "
                         f"(sweeps x points / time) "
                         f"max_err={err:.2e} ok={err < 1e-4}",
                         sweeps=s, mstencil_per_s=s * st1 / t / 1e6,
                         max_err=err, ok=bool(err < 1e-4)))
    return rows


def _plan_rows(rng) -> List[str]:
    """Direct vs CSE vs factored schedules for stencil27 -- the paper's
    synthesized-vs-naive comparison, with each plan's static op counts."""
    rows: List[str] = []
    m, n, p = 16, 24, 128
    w = jnp.asarray(rng.uniform(0.1, 1, (2, 2, 2)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((m, n, p)), jnp.float32)
    st = (m - 2) * (n - 2) * (p - 2)
    t_direct = None
    for kind in ("direct", "cse", "factored"):
        cplan = compile_plan("stencil27", kind)
        t = _time(lambda x, k=kind: stencil_apply(x, w, "stencil27",
                                                  block_i=4, plan=k), a)
        err = float(jnp.max(jnp.abs(
            stencil_apply(a, w, "stencil27", block_i=4, plan=kind)
            - stencil_ref(a, w, "stencil27", plan=kind))))
        t_direct = t_direct if t_direct is not None else t
        rows.append(_row(f"engine27.plan_{kind}.{m}x{n}x{p}", t * 1e6,
                         f"{st/t/1e6:.2f} Mstencil/s shifts={cplan.shifts} "
                         f"flops={cplan.flops} vs_direct={t_direct/t:.2f}x "
                         f"max_err={err:.2e} ok={err < 1e-4}",
                         plan=cplan.describe(), plan_kind=kind,
                         radius=list(cplan.spec.radius),
                         pass_list=list(cplan.passes),
                         mstencil_per_s=st / t / 1e6,
                         speedup_vs_direct=t_direct / t, max_err=err,
                         ok=bool(err < 1e-4)))
    return rows


def _radius_rows(rng) -> List[str]:
    """Radius-2 builtins (star13 / box125): streamed vs replicated with the
    radius-aware modeled bytes/point -- streaming stays ~2 x itemsize/point
    while the replicated path pays (2r+2) = 6 x -- plus parity against the
    reference."""
    rows: List[str] = []
    m, n, p, bi = 16, 24, 128, 4
    for name, wshape in (("star13", (3,)), ("box125", (3, 3, 3))):
        cplan = compile_plan(name)
        w = jnp.asarray(rng.uniform(0.1, 1, wshape), jnp.float32)
        a = jnp.asarray(rng.standard_normal((m, n, p)), jnp.float32)
        st = (m - 2) * (n - 2) * (p - 2)
        base = None
        for path in ("replicate", "stream"):
            bpp = bytes_per_point(path, 4, radius=2)
            t = _time(lambda x, pa=path: stencil_apply(
                x, w, name, block_i=bi, path=pa), a, reps=3)
            err = float(jnp.max(jnp.abs(
                stencil_apply(a, w, name, block_i=bi, path=path)
                - stencil_ref(a, w, name))))
            base = t if path == "replicate" else base
            rows.append(_row(
                f"engine_r2.{name}_{path}.{m}x{n}x{p}", t * 1e6,
                f"{st/t/1e6:.2f} Mstencil/s bytes_per_pt={bpp:.1f} "
                f"shifts={cplan.shifts} flops={cplan.flops} "
                f"vs_replicate={base/t:.2f}x max_err={err:.2e} "
                f"ok={err < 1e-3}",
                path=path, radius=list(cplan.spec.radius),
                pass_list=list(cplan.passes), bytes_per_point=bpp,
                plan=cplan.describe(), mstencil_per_s=st / t / 1e6,
                speedup_vs_replicate=base / t, max_err=err,
                ok=bool(err < 1e-3)))
    return rows


def _bc_rows(rng) -> List[str]:
    """Boundary-condition variants of the streamed 27-point kernel: the
    same plan and data movement under periodic (wrapped stream lead-in),
    neumann (mirror ghost fill), and dirichlet ghosts -- timed, and
    verified against the per-BC ``np.pad``-mode reference."""
    rows: List[str] = []
    m, n, p, bi = (REF_CONFIG[k] for k in ("m", "n", "p", "block_i"))
    w = jnp.asarray(rng.uniform(0.1, 1, (2, 2, 2)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((m, n, p)), jnp.float32)
    for bc in ("clamp", "periodic", "neumann", "dirichlet"):
        name = "stencil27" if bc == "clamp" else f"stencil27_{bc}"
        # non-clamp BCs update every point; clamp leaves the ring fixed
        st = (m - 2) * (n - 2) * (p - 2) if bc == "clamp" else m * n * p
        t = _time(lambda x, nm=name: stencil_apply(
            x, w, nm, block_i=bi, path="stream"), a, reps=3)
        err = float(jnp.max(jnp.abs(
            stencil_apply(a, w, name, block_i=bi, path="stream")
            - stencil_ref(a, w, name))))
        rows.append(_row(
            f"engine27.bc_{bc}.{m}x{n}x{p}", t * 1e6,
            f"{st/t/1e6:.2f} Mstencil/s bc={bc} max_err={err:.2e} "
            f"ok={err < 1e-4}",
            bc=bc, mstencil_per_s=st / t / 1e6, max_err=err,
            ok=bool(err < 1e-4)))
    return rows


# Reference 27-point configuration for the streamed-vs-replicated
# comparison and the CI cost-model gate.
REF_CONFIG = dict(m=16, n=24, p=128, block_i=4, itemsize=4)


def _path_rows(rng) -> List[str]:
    """Streamed vs replicated data movement for stencil27 -- the paper's
    plane-streaming kernel (each input plane fetched once, halo carried in
    VMEM scratch) against the halo-replicated one, with each path's modeled
    bytes/point, roofline, and achieved HBM bandwidth."""
    rows: List[str] = []
    m, n, p, bi = (REF_CONFIG[k] for k in ("m", "n", "p", "block_i"))
    w = jnp.asarray(rng.uniform(0.1, 1, (2, 2, 2)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((m, n, p)), jnp.float32)
    st = (m - 2) * (n - 2) * (p - 2)
    cplan = compile_plan("stencil27")
    itemsize = a.dtype.itemsize
    base = None
    for sweeps in (1, 2):
        for path in ("replicate", "stream"):
            bpp = bytes_per_point(path, itemsize, j_tiled=False,
                                  sweeps=sweeps)
            roof = streaming_roofline(bpp - itemsize / sweeps,
                                      itemsize / sweeps,
                                      (cplan.flops + cplan.shifts),
                                      HBM_BW, VPU_FLOPS)
            t = _time(lambda x, pa=path, s=sweeps: stencil_apply(
                x, w, "stencil27", block_i=bi, sweeps=s, path=pa), a)
            err = float(jnp.max(jnp.abs(
                stencil_apply(a, w, "stencil27", block_i=bi, sweeps=sweeps,
                              path=path)
                - stencil_ref(a, w, "stencil27", sweeps=sweeps))))
            moved = bpp * sweeps * m * n * p          # bytes per call
            gbps = moved / t / 1e9
            base = t if path == "replicate" else base
            rows.append(_row(
                f"engine27.path_{path}_s{sweeps}.{m}x{n}x{p}", t * 1e6,
                f"{sweeps*st/t/1e6:.2f} Mstencil/s "
                f"bytes_per_pt={bpp:.1f} achieved={gbps:.2f} GB/s "
                f"vs_replicate={base/t:.2f}x bound={roof.bound} "
                f"max_err={err:.2e} ok={err < 1e-4}",
                path=path, sweeps=sweeps, bytes_per_point=bpp,
                achieved_gbps=gbps, modeled_bound=roof.bound,
                mstencil_per_s=sweeps * st / t / 1e6,
                speedup_vs_replicate=base / t, max_err=err,
                ok=bool(err < 1e-4)))
    return rows


def _sweeps_rows(rng) -> List[str]:
    """Temporal-integration modes for ``s`` sweeps of stencil27: ``s``
    chained single-sweep calls (one HBM round-trip each) vs one fused
    ``sweeps=s`` call vs the temporal-wavefront pipeline, with each mode's
    modeled bytes/point, verified against the reference -- plus a
    red-black Gauss-Seidel run through the driver."""
    rows: List[str] = []
    m, n, p, itemsize = (REF_CONFIG[k] for k in ("m", "n", "p", "itemsize"))
    w = jnp.asarray(rng.uniform(0.1, 1, (2, 2, 2)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((m, n, p)), jnp.float32)
    s = 4
    st = (m - 2) * (n - 2) * (p - 2)
    ref = stencil_ref(a, w, "stencil27", sweeps=s)
    for mode in ("chained", "fused", "wavefront"):
        bpp = (2.0 * itemsize if mode == "chained"
               else 2.0 * itemsize / s)
        t = _time(lambda x, mo=mode: stencil_sweep_driver(
            x, w, "stencil27", sweeps=s, mode=mo), a, reps=3)
        err = float(jnp.max(jnp.abs(stencil_sweep_driver(
            a, w, "stencil27", sweeps=s, mode=mode) - ref)))
        rows.append(_row(
            f"engine27.sweeps_{mode}_s{s}.{m}x{n}x{p}", t * 1e6,
            f"{s*st/t/1e6:.2f} Mstencil/s bytes_per_pt={bpp:.1f} "
            f"max_err={err:.2e} ok={err < 1e-4}",
            mode=mode, sweeps=s, bytes_per_point=bpp,
            mstencil_per_s=s * st / t / 1e6, max_err=err,
            ok=bool(err < 1e-4)))
    # red-black Gauss-Seidel ordering through the auto-raced driver
    t = _time(lambda x: stencil_sweep_driver(
        x, w, "stencil27_redblack", sweeps=2), a, reps=3)
    err = float(jnp.max(jnp.abs(
        stencil_sweep_driver(a, w, "stencil27_redblack", sweeps=2)
        - stencil_ref(a, w, "stencil27_redblack", sweeps=2))))
    rows.append(_row(
        f"engine27.sweeps_redblack_s2.{m}x{n}x{p}", t * 1e6,
        f"{2*st/t/1e6:.2f} Mstencil/s ordering=redblack max_err={err:.2e} "
        f"ok={err < 1e-4}",
        ordering="redblack", sweeps=2, mstencil_per_s=2 * st / t / 1e6,
        max_err=err, ok=bool(err < 1e-4)))
    return rows


def check_wavefront_model() -> List[str]:
    """The CI gate (satellite): the temporal wavefront for stencil27 at
    s=4 must model bytes/point within 1.25 x of the ideal
    ``2 * itemsize / s`` and the sweeps-aware autotuner must not fall back
    to the chained per-sweep round-trip.  Appends a gate row; raises
    ``SystemExit(1)`` on violation so the workflow fails."""
    itemsize = REF_CONFIG["itemsize"]
    m, n, p = (REF_CONFIG[k] for k in ("m", "n", "p"))
    s = 4
    sel = autotune_sweeps(m, n, p, itemsize, s, compile_plan("stencil27"))
    wf = [c for c in sel.candidates if c[0] == "wavefront"]
    wf_bpp = wf[0][4] if wf else float("inf")
    limit = 1.25 * (2 * itemsize / s)
    ok = wf_bpp <= limit and sel.mode != "chained"
    rows = [_row("engine27.wavefront_gate", 0.0,
                 f"wavefront={wf_bpp:.2f} B/pt limit={limit:.2f} s={s} "
                 f"auto_mode={sel.mode} ok={ok}",
                 wavefront_bytes_per_point=wf_bpp, limit=limit, sweeps=s,
                 auto_mode=sel.mode, ok=bool(ok))]
    if not ok:
        print("\n".join(rows))
        write_json(default="BENCH_stencil.quick.json")
        raise SystemExit(
            f"stencil wavefront gate failed: stencil27 s={s} wavefront "
            f"modeled {wf_bpp} bytes/point (limit {limit}), auto mode "
            f"{sel.mode!r}")
    return rows


def check_guard_model() -> List[str]:
    """The CI gate (guarded-execution PR): the *default* guard policy's
    modeled check traffic -- :func:`guard_bytes_per_point`, the sampled
    NaN + invariant checks sharing one gathered strip per sampled plane --
    must cost < 10% of the streaming path's ``2 * itemsize`` bytes/point at
    the canonical serving depth ``m = GUARD_GATE_M``.  Appends a gate row;
    raises ``SystemExit(1)`` on violation so the workflow fails."""
    itemsize = REF_CONFIG["itemsize"]
    policy = GuardPolicy()
    g_bpp = guard_bytes_per_point(policy, itemsize, GUARD_GATE_M)
    stream = 2.0 * itemsize
    frac = g_bpp / stream
    ok = frac < 0.10
    rows = [_row("engine27.guard_gate", 0.0,
                 f"guard={g_bpp:.3f} B/pt stream={stream:.1f} B/pt "
                 f"fraction={frac:.3f} limit=0.10 m={GUARD_GATE_M} "
                 f"sample={policy.sample} ok={ok}",
                 guard_bytes_per_point=g_bpp,
                 stream_bytes_per_point=stream, fraction=frac,
                 gate_m=GUARD_GATE_M, sample=policy.sample, ok=bool(ok))]
    if not ok:
        print("\n".join(rows))
        write_json(default="BENCH_stencil.quick.json")
        raise SystemExit(
            f"stencil guard-overhead gate failed: default policy models "
            f"{g_bpp} bytes/point = {frac:.1%} of the streaming path's "
            f"{stream} (limit 10%) at m={GUARD_GATE_M}")
    return rows


def _guard_row(rng) -> str:
    """Measured guard overhead: the default sampled policy vs ``guard="off"``
    on the reference shape (interpret-mode wall clock is indicative only --
    the modeled fraction in ``check_guard_model`` is the gated number)."""
    m, n, p = (REF_CONFIG[k] for k in ("m", "n", "p"))
    a = jnp.asarray(rng.integers(-4, 5, size=(m, n, p)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.1, 1, (2, 2, 2)), jnp.float32)
    t_off = _time(lambda x: stencil_apply(x, w, "stencil27"), a, reps=3)
    policy = GuardPolicy()
    t_on = _time(lambda x: stencil_apply(x, w, "stencil27", guard=policy),
                 a, reps=3)
    g_bpp = guard_bytes_per_point(policy, 4, GUARD_GATE_M)
    frac = g_bpp / (2.0 * 4)
    return _row(
        "engine27.guard_overhead", t_on * 1e6,
        f"off={t_off * 1e6:.1f}us on={t_on * 1e6:.1f}us "
        f"modeled_check_bytes={g_bpp:.3f} B/pt "
        f"({frac:.1%} of stream @ m={GUARD_GATE_M})",
        us_off=t_off * 1e6, us_on=t_on * 1e6,
        guard_bytes_per_point=g_bpp, modeled_fraction=frac)


def check_stream_model() -> List[str]:
    """The CI gate (satellite): for the reference 27-point configuration the
    streamed path must model <= 2.5 x itemsize bytes/point at sweeps=1 and
    never regress above the replicated path -- and the same bound must hold
    at radius 2 (star13), where the replicated path pays 6 x itemsize.
    Appends gate rows; raises ``SystemExit(1)`` on violation so the
    workflow fails."""
    itemsize = REF_CONFIG["itemsize"]
    m, n, p = (REF_CONFIG[k] for k in ("m", "n", "p"))
    rows: List[str] = []
    failures: List[str] = []
    for label, name, radius in (("engine27.model_gate", "stencil27", 1),
                                ("engine_r2.model_gate", "star13", 2)):
        stream = bytes_per_point("stream", itemsize, radius=radius)
        rep = bytes_per_point("replicate", itemsize, radius=radius)
        path, bi, bj = autotune_engine(m, n, p, itemsize,
                                       plan=compile_plan(name))
        ok = (stream <= 2.5 * itemsize) and (stream <= rep) \
            and path == "stream"
        rows.append(_row(label, 0.0,
                         f"stream={stream:.1f} replicate={rep:.1f} B/pt "
                         f"limit={2.5 * itemsize:.1f} radius={radius} "
                         f"auto_path={path} ok={ok}",
                         stream_bytes_per_point=stream, radius=radius,
                         replicate_bytes_per_point=rep, auto_path=path,
                         ok=bool(ok)))
        if not ok:
            failures.append(
                f"{name} (radius {radius}): streamed bytes/point {stream} "
                f"vs replicated {rep} (limit {2.5 * itemsize}), auto path "
                f"{path!r}")
    if failures:
        # surface the diagnostics the gate exists for: the gate rows and the
        # measured rows recorded so far still reach stdout + the artifact
        print("\n".join(rows))
        write_json(default="BENCH_stencil.quick.json")
        raise SystemExit("stencil cost-model gate failed: "
                         + "; ".join(failures))
    return rows


def _jtiled_row(rng) -> str:
    """A size whose full N x P slab exceeds the VMEM budget: the cost model
    must pick a j-tiled blocking (previously a hard wall) and the result
    must still match the reference."""
    m, n, p = 4, 2048, 128
    cplan = compile_plan("stencil27")
    path, bi, bj = autotune_engine(m, n, p, 4, sweeps=1, plan=cplan)
    w = jnp.asarray(rng.uniform(0.1, 1, (2, 2, 2)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((m, n, p)), jnp.float32)
    st = (m - 2) * (n - 2) * (p - 2)
    t = _time(lambda x: stencil_apply(x, w, "stencil27"), a, reps=3)
    err = float(jnp.max(jnp.abs(stencil_apply(a, w, "stencil27")
                                - stencil_ref(a, w, "stencil27"))))
    return _row(f"engine27.jtiled.{m}x{n}x{p}", t * 1e6,
                f"{st/t/1e6:.2f} Mstencil/s path={path} blocks=({bi},{bj}) "
                f"max_err={err:.2e} ok={bj is not None and err < 1e-4}",
                path=path, block_i=bi, block_j=bj,
                mstencil_per_s=st / t / 1e6,
                max_err=err, ok=bool(bj is not None and err < 1e-4))


def _sharded_row() -> str:
    """Time the 2-device halo-exchange path on forced host devices."""
    code = """
        import time
        import jax, numpy as np, jax.numpy as jnp
        from repro.kernels import stencil_apply, stencil_sharded
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.standard_normal((16, 24, 128)), jnp.float32)
        w = jnp.asarray(rng.uniform(0.1, 1, (2, 2, 2)), jnp.float32)
        mesh = jax.make_mesh((2,), ("data",))
        run = lambda: stencil_sharded(a, w, "stencil27", mesh=mesh,
                                      sweeps=2).block_until_ready()
        run()                                   # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter(); run()
            best = min(best, time.perf_counter() - t0)
        one = stencil_apply(a, w, "stencil27", block_i=4, sweeps=2)
        err = float(jnp.max(jnp.abs(stencil_sharded(
            a, w, "stencil27", mesh=mesh, sweeps=2) - one)))
        st = 2 * 14 * 22 * 126
        print(f"engine27.sharded_2dev_s2.16x24x128,{best*1e6:.1f},"
              f"{st/best/1e6:.2f} Mstencil/s n_dev={jax.device_count()} "
              f"max_err_vs_single={err:.2e} ok={err < 1e-4}")
    """
    return _subprocess_rows(code, "engine27.sharded_2dev_s2.16x24x128",
                            n_dev=2)[0]


def _subprocess_rows(code: str, fallback_name: str, n_dev: int) -> List[str]:
    """Run ``code`` under ``n_dev`` forced host devices and parse every
    ``name,usec,derived`` stdout line into text rows + JSON records.

    The child is pinned to the CPU (``JAX_PLATFORMS=cpu``): this process
    already holds any accelerator, and these rows are host-device rows by
    design.  A child that fails, or prints a line that is not a row, raises
    -- the benchmark run then exits non-zero."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
        + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=600, env=env)
    if out.returncode != 0:
        err_lines = out.stderr.strip().splitlines() or ["(no stderr)"]
        raise RuntimeError(f"{fallback_name}: {n_dev}-device child exited "
                           f"{out.returncode}: {err_lines[-1][:200]}")
    rows = []
    for line in out.stdout.strip().splitlines() or ["(no stdout)"]:
        parts = line.split(",", 2)
        if len(parts) != 3:
            raise RuntimeError(f"{fallback_name}: unparseable row from the "
                               f"{n_dev}-device child: {line[:200]}")
        name, usec, derived = parts
        _RECORDS.append({"name": name, "us_per_call": float(usec),
                         "ok": "ok=True" in derived, "derived": derived})
        rows.append(line)
    return rows


def _sharded_grid_rows() -> List[str]:
    """The multi-axis grid on 8 forced host devices: a 2x2x2 stencil27 run
    with the serialized exchange (``overlap="off"``) and the
    compute/communication-overlap schedule (``overlap="on"``), both checked
    against the single-device oracle.  Timing rows (never gated -- host
    devices on a CI runner measure scheduling, not bandwidth); correctness
    ``ok`` flags ride the ``derived`` column like the other sharded row."""
    code = """
        import time
        import jax, numpy as np, jax.numpy as jnp
        from repro.kernels import stencil_apply, stencil_sharded
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.integers(-4, 5, (32, 32, 64)), jnp.float32)
        w = jnp.asarray(rng.integers(-3, 4, (2, 2, 2)), jnp.float32)
        mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"))
        one = stencil_apply(a, w, "stencil27", sweeps=2)
        for overlap in ("off", "on"):
            run = lambda: stencil_sharded(
                a, w, "stencil27", mesh=mesh, axes=("x", "y", "z"),
                sweeps=2, overlap=overlap).block_until_ready()
            got = run()                             # compile + warm
            err = float(jnp.max(jnp.abs(got - one)))
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter(); run()
                best = min(best, time.perf_counter() - t0)
            st = 2 * 30 * 30 * 62
            print(f"engine27.grid_2x2x2_s2_overlap_{overlap}.32x32x64,"
                  f"{best*1e6:.1f},{st/best/1e6:.2f} Mstencil/s "
                  f"n_dev={jax.device_count()} max_err_vs_single={err:.2e} "
                  f"ok={err == 0.0}")
    """
    return _subprocess_rows(code, "engine27.grid_2x2x2_s2.32x32x64", n_dev=8)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    quick = "--quick" in sys.argv[1:]
    print("\n".join(run_quick() if quick else run()))
