"""Boundary-condition parity suite (the BC tentpole's acceptance tests).

Every BC x radius {1, 2} x path {stream, replicate} is checked bit-exactly
against an *independent* NumPy ``np.pad`` oracle on f64 integer-valued data
(exact arithmetic, so tap-order reassociation can't hide a wrong ghost) and
to tolerance on f32/bf16; plus fused sweeps {1, 3}, j-tiling, the engine's
own jnp reference, per-axis-side mixes, the BC-suffixed registry builtins,
spec validation errors, and a 2-device periodic wrap-around sharded
subprocess test (the halo ring)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _x64 import x64

from repro.kernels import (BC, dirichlet, get_stencil, spec_from_mask,
                           stencil_apply, stencil_ref, stencil_sharded)
from repro.kernels.stencil_engine.spec import as_boundary, bc_labels

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(17)

_PAD_MODE = {"periodic": "wrap", "neumann": "symmetric"}


def np_pad_oracle(a, w, spec, sweeps=1):
    """Independent oracle: per sweep, ``np.pad`` the trailing ``ndim`` axes
    by ``radius`` under the per-axis-side modes (axes in i, j, k order),
    take the direct tap sum on the padded field, and zero the one-point
    ring of clamp sides.  Pure NumPy f64 -- shares no code with the
    engine."""
    u = np.asarray(a, np.float64)
    wf = np.asarray(w, np.float64).reshape(-1)
    nd = spec.ndim
    lead = u.ndim - nd
    for _ in range(sweeps):
        up = u
        for ax in range(3 - nd, 3):
            r = spec.radius[ax]
            if r == 0:
                continue
            axis = lead + (ax - (3 - nd))
            lo, hi = spec.bc[ax]
            if lo.kind == "periodic":
                pw = [(0, 0)] * up.ndim
                pw[axis] = (r, r)
                up = np.pad(up, pw, mode="wrap")
                continue
            for side, width in ((lo, (r, 0)), (hi, (0, r))):
                pw = [(0, 0)] * up.ndim
                pw[axis] = width
                if side.kind == "clamp":
                    up = np.pad(up, pw, mode="constant")
                elif side.kind == "dirichlet":
                    up = np.pad(up, pw, mode="constant",
                                constant_values=side.value)
                else:
                    up = np.pad(up, pw, mode=_PAD_MODE[side.kind])
        out = np.zeros_like(u)
        for off, widx in zip(spec.offsets, spec.w_index):
            sl = [slice(None)] * lead
            for ax in range(3 - nd, 3):
                axis = lead + (ax - (3 - nd))
                r, d = spec.radius[ax], off[ax]
                sl.append(slice(r + d, r + d + u.shape[axis]))
            out += wf[widx] * up[tuple(sl)]
        for ax in range(3 - nd, 3):
            axis = lead + (ax - (3 - nd))
            lo, hi = spec.bc[ax]
            if lo.kind == "clamp":
                s = [slice(None)] * u.ndim
                s[axis] = 0
                out[tuple(s)] = 0
            if hi.kind == "clamp":
                s = [slice(None)] * u.ndim
                s[axis] = -1
                out[tuple(s)] = 0
        u = out
    return u


def _int_data(shape, dtype=jnp.float64):
    return jnp.asarray(RNG.integers(-4, 5, shape), dtype)


def _int_weights(spec, dtype=jnp.float64):
    return jnp.asarray(RNG.integers(1, 4, spec.w_shape), dtype)


@pytest.mark.parametrize("name,block_i", [("stencil27", 4), ("star13", 6)])
@pytest.mark.parametrize("bc", ["clamp", "periodic", "neumann",
                                dirichlet(2.0)])
@pytest.mark.parametrize("sweeps", [1, 3])
@pytest.mark.parametrize("path", ["stream", "replicate"])
def test_bc_bit_exact_vs_np_pad_oracle(name, block_i, bc, sweeps, path):
    """Acceptance: periodic / dirichlet / neumann (and the clamp default)
    agree bit-exactly (f64, integer-valued data) with the NumPy np.pad
    reference across radius {1, 2} x path {stream, replicate} x sweeps
    {1, 3} -- and so does the engine's own jnp reference."""
    spec = get_stencil(name).with_bc(bc)
    with x64():
        a = _int_data((12, 12, 16))
        w = _int_weights(spec)
        want = np_pad_oracle(a, w, spec, sweeps=sweeps)
        got = np.asarray(stencil_apply(a, w, spec, block_i=block_i,
                                       sweeps=sweeps, path=path))
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            np.asarray(stencil_ref(a, w, spec, sweeps=sweeps)), want)


@pytest.mark.parametrize("name", ["stencil27", "star13"])
@pytest.mark.parametrize("bc", ["periodic", "neumann", dirichlet(0.0)])
def test_bc_jtiled_bit_exact(name, bc):
    """j-tiled blocking under every BC is bit-identical to the untiled run
    and to the oracle (the tiled j axis realizes its BC by halo fill /
    wrapped index maps instead of in-shift fill)."""
    spec = get_stencil(name).with_bc(bc)
    bi = 4 if spec.radius[0] == 1 else 6
    with x64():
        a = _int_data((12, 12, 16))
        w = _int_weights(spec)
        want = np_pad_oracle(a, w, spec)
        for path in ("stream", "replicate"):
            for bj in (4, 6):
                got = np.asarray(stencil_apply(a, w, spec, block_i=bi,
                                               block_j=bj, path=path))
                np.testing.assert_array_equal(got, want)


def test_bc_mixed_per_axis_and_per_side():
    """Per-axis-side mixes: periodic i, (neumann, dirichlet) j, clamp k --
    and an asymmetric ad-hoc mask (cse plan) under periodic BCs."""
    mix = ("periodic", ("neumann", "dirichlet"), "clamp")
    spec = get_stencil("stencil27").with_bc(mix)
    with x64():
        a = _int_data((8, 12, 16))
        w = _int_weights(spec)
        want = np_pad_oracle(a, w, spec, sweeps=2)
        for path in ("stream", "replicate"):
            got = np.asarray(stencil_apply(a, w, spec, block_i=4, sweeps=2,
                                           path=path))
            np.testing.assert_array_equal(got, want)
        mask = np.zeros((3, 3, 3), bool)
        mask[1, 1, 1] = mask[2, 0, 1] = mask[1, 2, 2] = mask[0, 1, 0] = True
        asym = spec_from_mask("bc-asym", mask, bc="periodic")
        aw = jnp.asarray(RNG.integers(1, 4, asym.w_shape), jnp.float64)
        want = np_pad_oracle(a, aw, asym)
        for path in ("stream", "replicate"):
            got = np.asarray(stencil_apply(a, aw, asym, block_i=4,
                                           path=path))
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("bc", ["periodic", "neumann", dirichlet(1.0)])
def test_bc_float_tolerance(dtype, tol, bc):
    """f32/bf16 runs agree with the f64 oracle to accumulation tolerance on
    float data, across both paths (the engine accumulates in f32; atol is
    scaled by the field magnitude -- two fused sweeps grow values to
    ~1e2)."""
    spec = get_stencil("stencil27").with_bc(bc)
    a = jnp.asarray(RNG.standard_normal((8, 12, 16)), dtype)
    w = jnp.asarray(RNG.uniform(0.1, 1.0, spec.w_shape), dtype)
    want = np_pad_oracle(np.asarray(a, np.float64),
                         np.asarray(w, np.float64), spec, sweeps=2)
    scale = float(np.abs(want).max())
    for path in ("stream", "replicate"):
        got = np.asarray(stencil_apply(a, w, spec, block_i=4, sweeps=2,
                                       path=path), np.float32)
        np.testing.assert_allclose(got, want, rtol=10 * tol,
                                   atol=tol * scale)


def test_bc_1d_stencil3():
    """The k-only path realizes its BC in the shift primitive; the
    BC-suffixed stencil3 builtins match the oracle."""
    a = _int_data((6, 32), jnp.float32)
    w = jnp.asarray(RNG.integers(1, 4, (2,)), jnp.float32)
    for tag in ("stencil3", "stencil3_periodic", "stencil3_neumann",
                "stencil3_dirichlet"):
        spec = get_stencil(tag)
        want = np_pad_oracle(a, w, spec, sweeps=2).astype(np.float32)
        got = np.asarray(stencil_apply(a, w, tag, sweeps=2))
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            np.asarray(stencil_ref(a, w, tag, sweeps=2)), want)
    # hand check: periodic really wraps
    u = np.asarray(a, np.float64)
    wf = np.asarray(w, np.float64)
    want = wf[1] * u + wf[0] * (np.roll(u, 1, -1) + np.roll(u, -1, -1))
    np.testing.assert_array_equal(
        np.asarray(stencil_apply(a, w, "stencil3_periodic")),
        want.astype(np.float32))


def test_bc_registry_builtins_and_describe():
    """BC-suffixed builtins are registered for every base spec, carry the
    right per-axis BCs, and their plans memoize separately from (and
    describe differently to) the clamp default."""
    from repro.kernels import compile_plan
    for base in ("stencil7", "stencil27", "star13", "box125"):
        spec = get_stencil(f"{base}_periodic")
        assert all(s.kind == "periodic" for ax in spec.bc for s in ax)
        assert spec.offsets == get_stencil(base).offsets
        d = compile_plan(spec).describe()
        assert d["bc"] == ["periodic"] * 3
        # same tap schedule, distinct memo entry
        base_plan = compile_plan(base)
        assert compile_plan(spec) is not base_plan
        assert compile_plan(spec).ops == base_plan.ops
    assert get_stencil("stencil3_neumann").bc[2][0].kind == "neumann"
    assert bc_labels(as_boundary(dirichlet(2.0)))[0] == "dirichlet(2)"
    assert bc_labels(as_boundary(("clamp", ("periodic", "periodic"),
                                  "neumann"))) == ("clamp", "periodic",
                                                   "neumann")


def test_bc_validation_errors():
    spec = get_stencil("stencil27")
    with pytest.raises(ValueError, match="periodic must be paired"):
        spec.with_bc((("periodic", "clamp"), "clamp", "clamp"))
    with pytest.raises(ValueError, match="distinct dirichlet values"):
        spec.with_bc((dirichlet(1.0), dirichlet(2.0), "clamp"))
    with pytest.raises(ValueError, match="unknown BC kind"):
        spec.with_bc("warp")
    with pytest.raises(ValueError, match="k-axis"):
        get_stencil("stencil3").with_bc("periodic")
    # nonzero dirichlet ghosts can't meet a radius-2 clamp side
    with pytest.raises(ValueError, match="nonzero ghost value"):
        get_stencil("star13").with_bc((dirichlet(2.0), "clamp", "clamp"))
    # ...but dirichlet(0) can, and radius-1 mixes are fine
    get_stencil("star13").with_bc((dirichlet(0.0), "clamp", "clamp"))
    get_stencil("stencil27").with_bc((dirichlet(2.0), "clamp", "clamp"))
    with pytest.raises((TypeError, ValueError)):
        spec.with_bc(("clamp", "clamp"))          # not 3 axes


def test_bc_default_clamp_unchanged():
    """with_bc("clamp") is the default spec: same results, same plan memo
    entry (the BC refactor must not perturb the engine's historical
    semantics)."""
    from repro.kernels import compile_plan
    spec = get_stencil("stencil27")
    assert spec.with_bc("clamp") == spec
    assert compile_plan(spec.with_bc("clamp")) is compile_plan(spec)
    a = _int_data((8, 12, 16), jnp.float32)
    w = jnp.asarray(RNG.integers(1, 4, (2, 2, 2)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(stencil_apply(a, w, "stencil27", block_i=4)),
        np.asarray(stencil_apply(a, w, "stencil27", block_i=4, bc="clamp")))


def test_bc_periodic_sharded_two_devices_subprocess():
    """Acceptance: the 2-device periodic wrap-around sharded run (the halo
    exchange becomes a ring -- shard 0 trades rows with shard N-1) is
    bit-identical to the single-device periodic run, on both paths, radius
    1 and 2 -- and dirichlet/neumann edge shards stay exact too."""
    code = """
        import jax, numpy as np, jax.numpy as jnp
        assert jax.device_count() == 2, jax.devices()
        from repro.kernels import (dirichlet, stencil_apply, stencil_sharded,
                                   get_stencil)
        from repro.sharding.planner import stencil_grid_sharding
        rng = np.random.default_rng(7)
        a = jnp.asarray(rng.integers(-4, 5, (16, 12, 16)), jnp.float32)
        mesh = jax.make_mesh((2,), ("data",))
        plan = stencil_grid_sharding((16, 12, 16), mesh,
                                     axes=("data", None, None), sweeps=2,
                                     radius=2, periodic=(True, True, True))
        assert plan.periodic[0] and "ring" in plan.notes[-1].reason
        for name in ("stencil27", "star13"):
            spec = get_stencil(name)
            w = jnp.asarray(rng.integers(1, 4, spec.w_shape), jnp.float32)
            bcs = ["periodic", "neumann"] + (
                [dirichlet(2.0)] if name == "stencil27" else [])
            for bc in bcs:
                for s in (1, 2):
                    for path in ("stream", "replicate"):
                        sh = stencil_sharded(a, w, name, mesh=mesh, sweeps=s,
                                             path=path, bc=bc)
                        one = stencil_apply(a, w, name, block_i=4, sweeps=s,
                                            path=path, bc=bc)
                        np.testing.assert_array_equal(np.asarray(sh),
                                                      np.asarray(one))
        print("bc sharded ok")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "bc sharded ok" in out.stdout


def test_bc_sharded_single_device_fallback():
    """The unsharded fallback threads the BC override through to
    stencil_apply (no silent clamp regression when the planner declines)."""
    a = _int_data((7, 12, 16), jnp.float32)   # M=7 indivisible -> fallback
    w = jnp.asarray(RNG.integers(1, 4, (2, 2, 2)), jnp.float32)
    got = stencil_sharded(a, w, "stencil27", bc="periodic")
    want = stencil_apply(a, w, "stencil27", bc="periodic")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
