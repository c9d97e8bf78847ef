"""Variable-coefficient acceptance: per-point weight fields are bit-exact
against an independent (non-engine) numpy oracle and against the engine
reference, across data-movement path in {stream, replicate} x fused sweeps
in {1, 3}, j-tiled and untiled, broadcast weights, BC overrides, radius 2,
1-D specs, the autotuner's traffic accounting, and a 2-device halo-exchange
sharded run (subprocess).  Integer-valued data makes every reassociation
exact, so the comparisons are ``assert_array_equal``, not allclose."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _x64 import x64

from repro.kernels import get_stencil, stencil_apply, stencil_ref
from repro.kernels.stencil_engine.autotune import bytes_per_point

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(11)


def _ints(shape, lo=-3, hi=4):
    return RNG.integers(lo, hi, shape).astype(np.float64)


def _wints(shape):
    return RNG.integers(1, 4, shape).astype(np.float64)


def _oracle_var(u, wf, spec, sweeps=1):
    """Independent triple-loop oracle under the engine's clamp semantics:
    reads outside the domain are zero, the one-point output ring is zeroed,
    and coefficients are read at the *output* point."""
    nd = spec.ndim
    shape = u.shape
    cur = np.asarray(u, np.float64)
    wf = np.asarray(wf, np.float64)
    for _ in range(sweeps):
        out = np.zeros_like(cur)
        for idx in np.ndindex(*shape):
            if any(idx[a] in (0, shape[a] - 1) for a in range(nd)):
                continue
            s = 0.0
            for off, wi in zip(spec.offsets, spec.w_index):
                o = off[3 - nd:]
                src = tuple(idx[a] + o[a] for a in range(nd))
                if any(t < 0 or t >= shape[a]
                       for a, t in enumerate(src)):
                    continue
                s += wf[wi][idx] * cur[src]
            out[idx] = s
        cur = out
    return cur


@pytest.mark.parametrize("sweeps", [1, 2])
def test_var27_matches_independent_oracle(sweeps):
    """Non-circular: kernel AND engine ref against a hand-rolled loop."""
    spec = get_stencil("stencil27").with_coef("var")
    shape = (5, 6, 8)
    with x64():
        a = jnp.asarray(_ints(shape))
        w = jnp.asarray(_wints((spec.n_weights,) + shape))
        want = _oracle_var(a, w, spec, sweeps)
        ref = stencil_ref(a, w, spec, sweeps=sweeps)
        got = stencil_apply(a, w, spec, block_i=None, sweeps=sweeps)
        np.testing.assert_array_equal(np.asarray(ref), want)
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("name", ["stencil7", "stencil27"])
@pytest.mark.parametrize("path", ["stream", "replicate"])
@pytest.mark.parametrize("sweeps", [1, 3])
@pytest.mark.parametrize("block_j", [None, 4])
def test_var_paths_sweeps_bitexact_vs_ref(name, path, sweeps, block_j):
    spec = get_stencil(name).with_coef("var")
    shape = (8, 12, 16)
    with x64():
        a = jnp.asarray(_ints(shape))
        w = jnp.asarray(_wints((spec.n_weights,) + shape))
        got = stencil_apply(a, w, spec, block_i=4, block_j=block_j,
                            sweeps=sweeps, path=path)
        ref = stencil_ref(a, w, spec, sweeps=sweeps)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_var_broadcast_weights_equal_materialized():
    """(nw, 1, 1, P) weights broadcast over the domain == the same weights
    fully materialized to (nw, M, N, P)."""
    spec = get_stencil("stencil27").with_coef("var")
    m, n, p = 8, 10, 16
    with x64():
        a = jnp.asarray(_ints((m, n, p)))
        wb = jnp.asarray(_wints((spec.n_weights, 1, 1, p)))
        wfull = jnp.broadcast_to(wb, (spec.n_weights, m, n, p))
        for path in ("stream", "replicate"):
            np.testing.assert_array_equal(
                np.asarray(stencil_apply(a, wb, spec, block_i=4, path=path)),
                np.asarray(stencil_apply(a, wfull, spec, block_i=4,
                                         path=path)))


@pytest.mark.parametrize("path", ["stream", "replicate"])
def test_var_radius2_star13(path):
    spec = get_stencil("star13").with_coef("var")
    shape = (10, 12, 16)
    with x64():
        a = jnp.asarray(_ints(shape))
        w = jnp.asarray(_wints((spec.n_weights,) + shape))
        for bj in (None, 4):
            got = stencil_apply(a, w, spec, block_i=5, block_j=bj,
                                sweeps=2, path=path)
            ref = stencil_ref(a, w, spec, sweeps=2)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("bc", ["periodic", "neumann", "dirichlet"])
@pytest.mark.parametrize("path", ["stream", "replicate"])
def test_var_boundary_conditions(bc, path):
    from repro.kernels import dirichlet
    over = dirichlet(2.0) if bc == "dirichlet" else bc
    spec = get_stencil("stencil27").with_coef("var").with_bc(over)
    shape = (8, 10, 16)
    with x64():
        a = jnp.asarray(_ints(shape))
        w = jnp.asarray(_wints((spec.n_weights,) + shape))
        got = stencil_apply(a, w, spec, block_i=4, sweeps=2, path=path)
        ref = stencil_ref(a, w, spec, sweeps=2)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_var_1d_stencil3():
    spec = get_stencil("stencil3").with_coef("var")
    with x64():
        a = jnp.asarray(_ints((6, 32)))
        w = jnp.asarray(_wints((spec.n_weights, 32)))
        got = stencil_apply(a, w, spec, block_i=3)
        ref = stencil_ref(a, w, spec)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        # one row through the independent oracle too
        want = _oracle_var(np.asarray(a[0]), np.asarray(w), spec)
        np.testing.assert_array_equal(np.asarray(got[0]), want)


def test_var_bytes_per_point_accounting():
    """Streaming untiled var traffic = (2 + n_weights) transfers/point
    (paper's ~2/point plus one co-streamed plane per weight field);
    constant coefficients move nothing extra."""
    for nw in (4, 8):
        base = bytes_per_point("stream", 4)
        var = bytes_per_point("stream", 4, coef="var", n_weights=nw)
        assert base == 2 * 4
        assert var == (2 + nw) * 4
        # replicated untiled at radius 1: every one of the 2ri+1 staged
        # views drags its own copy of the nw coefficient planes
        rep = bytes_per_point("replicate", 4, coef="var", n_weights=nw)
        assert rep == (4 + 3 * nw) * 4
        # amortized over fused sweeps like the field traffic
        assert bytes_per_point("stream", 4, sweeps=2, coef="var",
                               n_weights=nw) == var / 2


def test_var_sharded_two_devices_subprocess():
    """2-device halo-exchange with per-point coefficients sharded alongside
    the domain == the single-device engine, bit-exact -- chain topology and
    the periodic ring (which must exchange true wrapped coefficients)."""
    code = """
        import jax, numpy as np, jax.numpy as jnp
        assert jax.device_count() == 2, jax.devices()
        from repro.kernels import (get_stencil, stencil_apply, stencil_ref,
                                   stencil_sharded)
        rng = np.random.default_rng(5)
        mesh = jax.make_mesh((2,), ("data",))
        m, n, p = 16, 10, 16
        for bc in (None, "periodic"):
            spec = get_stencil("stencil27").with_coef("var")
            if bc is not None:
                spec = spec.with_bc(bc)
            a = jnp.asarray(rng.integers(-3, 4, (m, n, p)), jnp.float32)
            w = jnp.asarray(rng.integers(1, 4, (spec.n_weights, m, n, p)),
                            jnp.float32)
            for s in (1, 2):
                got = stencil_sharded(a, w, spec, mesh=mesh, sweeps=s)
                one = stencil_apply(a, w, spec, block_i=4, sweeps=s)
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(one))
                ref = stencil_ref(a, w, spec, sweeps=s)
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(ref))
        print("var sharded ok")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "var sharded ok" in out.stdout
