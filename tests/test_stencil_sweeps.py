"""Temporal wavefront tiling + red-black ordering: sweep-composition
property tests (s chained calls == one fused sweeps=s call == wavefront
driver, bit-exact on integer f64 across BC x path x radius), the
sweeps-aware autotuner race, the red-black kernel-vs-oracle parity, the
2-device deep-halo sharded run (subprocess), and the regression gate's
new-row semantics."""

import importlib.util
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _x64 import x64

from repro.kernels import (SWEEP_MODES, autotune_sweeps, compile_plan,
                           get_stencil, stencil_apply, stencil_ref,
                           stencil_sweep_driver, stencil_wavefront)
from repro.kernels.stencil_engine.autotune import wavefront_block_i
from repro.kernels.stencil_engine.spec import ORDERING_KINDS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(11)


def _int_field(shape):
    """Integer-valued f64 data: every reassociation/blocking is exact, so
    cross-mode comparisons can be ``assert_array_equal``."""
    return jnp.asarray(RNG.integers(-4, 5, shape).astype(np.float64))


def _int_weights(n):
    return jnp.asarray(RNG.integers(-3, 4, n).astype(np.float64))


SWEEP_SPECS = [
    ("stencil27", 8), ("stencil27_periodic", 8), ("stencil27_neumann", 8),
    ("stencil27_dirichlet", 8), ("star13", 3), ("star13_periodic", 3),
]


@pytest.mark.parametrize("name,nw", SWEEP_SPECS)
@pytest.mark.parametrize("s", [2, 4])
def test_sweep_composition_bit_exact(name, nw, s):
    """s chained calls == one fused sweeps=s call == the wavefront driver
    == the oracle, bit-exact, across BC x radius x s."""
    with x64():
        a = _int_field((12, 8, 32))
        w = _int_weights(nw)
        chained = a
        for _ in range(s):
            chained = stencil_apply(chained, w, name, sweeps=1)
        fused = stencil_apply(a, w, name, sweeps=s)
        wave = stencil_wavefront(a, w, name, sweeps=s)
        ref = stencil_ref(a, w, name, sweeps=s)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(chained))
        np.testing.assert_array_equal(np.asarray(wave), np.asarray(chained))
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(chained))


def test_sweep_composition_across_paths():
    """The chained oracle is path-invariant, and the wavefront matches it
    whichever path produced it (stream vs replicate)."""
    with x64():
        a = _int_field((12, 8, 32))
        w = _int_weights(8)
        wave = stencil_wavefront(a, w, "stencil27", sweeps=3)
        for path in ("stream", "replicate"):
            chained = a
            for _ in range(3):
                chained = stencil_apply(chained, w, "stencil27", sweeps=1,
                                        path=path)
            np.testing.assert_array_equal(np.asarray(wave),
                                          np.asarray(chained))


@pytest.mark.parametrize("mode", ["auto", "fused", "wavefront", "chained"])
def test_driver_modes_agree(mode):
    with x64():
        a = _int_field((12, 8, 32))
        w = _int_weights(8)
        ref = stencil_ref(a, w, "stencil27", sweeps=4)
        got = stencil_sweep_driver(a, w, "stencil27", sweeps=4, mode=mode)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_driver_batched_and_block_pins():
    with x64():
        a = _int_field((2, 12, 8, 32))
        w = _int_weights(8)
        ref = stencil_ref(a, w, "stencil27", sweeps=2)
        got = stencil_sweep_driver(a, w, "stencil27", sweeps=2,
                                   mode="wavefront", block_i=4)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_redblack_kernel_matches_oracle():
    """Red-black Gauss-Seidel: masked half-sweeps in the kernel == the
    NumPy-oracle checkerboard, for 3-D and 1-D specs, all modes."""
    with x64():
        a = _int_field((12, 8, 32))
        w = _int_weights(8)
        ref = stencil_ref(a, w, "stencil27_redblack", sweeps=2)
        for mode in ("fused", "wavefront", "chained"):
            got = stencil_sweep_driver(a, w, "stencil27_redblack", sweeps=2,
                                       mode=mode)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        # red-black genuinely differs from Jacobi on the same data
        jac = stencil_ref(a, w, "stencil27", sweeps=2)
        assert np.abs(np.asarray(ref) - np.asarray(jac)).max() > 0
        # 1-D (k-only) parity
        a1 = _int_field((6, 32))
        w1 = _int_weights(2)
        got1 = stencil_apply(a1, w1, "stencil3_redblack", sweeps=2)
        ref1 = stencil_ref(a1, w1, "stencil3_redblack", sweeps=2)
        np.testing.assert_array_equal(np.asarray(got1), np.asarray(ref1))


def test_redblack_spec_properties():
    spec = get_stencil("stencil27")
    rb = spec.with_ordering("redblack")
    assert rb.ordering == "redblack" and rb.sweep_apps == 2
    assert spec.sweep_apps == 1 and "redblack" in ORDERING_KINDS
    assert get_stencil("stencil27_redblack").ordering == "redblack"
    assert compile_plan(rb).describe()["ordering"] == "redblack"
    with pytest.raises(ValueError, match="ordering"):
        spec.with_ordering("zebra")


def test_autotune_sweeps_race():
    """The sweeps-aware roofline: wavefront or fused wins at s > 1 (both
    model 2*itemsize/s bytes/point vs 2*itemsize chained), fused wins the
    s=1 tie, and the verdict is recorded in describe()["selection"]."""
    cplan = compile_plan("stencil27")
    sel = autotune_sweeps(16, 24, 128, 4, 4, cplan)
    assert sel.mode in ("wavefront", "fused") and sel.sweeps == 4
    assert sel.bytes_per_point == pytest.approx(2.0)
    d = sel.describe()["selection"]
    assert d["mode"] == sel.mode
    assert {c["mode"] for c in d["candidates"]} == {"fused", "wavefront",
                                                    "chained"}
    chained = next(c for c in d["candidates"] if c["mode"] == "chained")
    assert chained["bytes_per_point"] == pytest.approx(8.0)
    assert autotune_sweeps(16, 24, 128, 4, 1, cplan).mode == "fused"
    # variable coefficients: the wavefront entrant drops out / refuses
    var = compile_plan(get_stencil("stencil27").with_coef("var"))
    assert autotune_sweeps(16, 24, 128, 4, 4, var).mode != "wavefront"
    with pytest.raises(ValueError, match="wavefront"):
        autotune_sweeps(16, 24, 128, 4, 4, var, mode="wavefront")
    with pytest.raises(ValueError, match="mode"):
        autotune_sweeps(16, 24, 128, 4, 2, cplan, mode="sideways")
    assert "auto" in SWEEP_MODES
    bi = wavefront_block_i(16, 24, 128, 4, 4, cplan)
    assert 16 % bi == 0 and bi >= 1


def test_wavefront_input_validation():
    with x64():
        a1 = _int_field((6, 32))
        with pytest.raises(ValueError, match="volumetric"):
            stencil_wavefront(a1, _int_weights(2), "stencil3", sweeps=2)
        # periodic deep halo must fit the domain
        a = _int_field((4, 8, 32))
        with pytest.raises(ValueError, match="halo"):
            stencil_wavefront(a, _int_weights(8), "stencil27_periodic",
                              sweeps=8)
        with pytest.raises(ValueError, match="mode"):
            stencil_sweep_driver(a, _int_weights(8), "stencil27",
                                 sweeps=2, mode="sideways")


def test_sharded_deep_halo_2dev_subprocess():
    """2 forced host devices: one radius*sweep_apps*s-deep halo exchange +
    redundant boundary recompute (fused and wavefront modes) matches the
    single-device chained oracle bit-exactly on integer f64."""
    code = """
    import jax, numpy as np, jax.numpy as jnp
    from repro.kernels import stencil_apply, stencil_sharded
    assert jax.device_count() == 2
    with jax.enable_x64(True):
        rng = np.random.default_rng(5)
        a = jnp.asarray(rng.integers(-4, 5, (16, 8, 32)).astype(np.float64))
        w8 = jnp.asarray(rng.integers(-3, 4, 8).astype(np.float64))
        w3 = jnp.asarray(rng.integers(-3, 4, 3).astype(np.float64))
        mesh = jax.make_mesh((2,), ("data",))
        for name, w, s in (("stencil27", w8, 2), ("stencil27", w8, 4),
                           ("stencil27_periodic", w8, 2),
                           ("star13_neumann", w3, 2),
                           ("stencil27_redblack", w8, 2)):
            chained = a
            for _ in range(s):
                chained = stencil_apply(chained, w, name, sweeps=1)
            for mode in ("fused", "wavefront", "auto"):
                got = stencil_sharded(a, w, name, mesh=mesh, sweeps=s,
                                      mode=mode)
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(chained))
        print("deep-halo ok")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "deep-halo ok" in out.stdout


def _load_check_regression():
    path = os.path.join(REPO, "benchmarks", "check_regression.py")
    mod_spec = importlib.util.spec_from_file_location("check_regression",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def test_regression_gate_new_rows_are_notes_not_failures():
    """Satellite: fresh-only rows (new wavefront/ordering entries) report
    as 'new, not gated yet'; baseline rows must still be present; an
    unjustified sweep-mode flip fails."""
    cr = _load_check_regression()
    sweeps_entry = {"mode": "wavefront", "bytes_per_point": 2.0,
                    "time_per_point": 1e-11,
                    "candidates": [
                        {"mode": "wavefront", "bytes_per_point": 2.0,
                         "time_per_point": 1e-11},
                        {"mode": "chained", "bytes_per_point": 8.0,
                         "time_per_point": 9e-12}]}
    base = {"schema": "bench_stencil/v5",
            "paths": {"stream": {"bytes_per_point_f32": 8.0}},
            "sweeps": {"stencil27/s4": sweeps_entry}}
    fresh = {"schema": "bench_stencil/v5",
             "paths": {"stream": {"bytes_per_point_f32": 8.0}},
             "sweeps": {"stencil27/s4": sweeps_entry,
                        "box125/s4": dict(sweeps_entry)}}
    failures, notes = cr.compare(base, fresh, 0.05)
    assert not failures
    assert any("box125/s4" in n and "not gated" in n for n in notes)
    # baseline row disappearing is still a failure
    failures, _ = cr.compare(fresh, base, 0.05)
    assert any("box125/s4" in f for f in failures)
    # a flip the fresh race argues against fails
    flipped = {"schema": "bench_stencil/v5",
               "paths": {"stream": {"bytes_per_point_f32": 8.0}},
               "sweeps": {"stencil27/s4": {
                   "mode": "chained", "bytes_per_point": 8.0,
                   "time_per_point": 9e-12,
                   "candidates": sweeps_entry["candidates"]}}}
    failures, _ = cr.compare(base, flipped, 0.05)
    assert any("flipped" in f or "bytes/point" in f for f in failures)
