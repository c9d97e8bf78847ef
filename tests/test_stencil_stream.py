"""Plane-streaming path: parity against the replicated escape hatch
(bit-exact on f64 / integer-valued data, to tolerance in f32/bf16) across
masks x sweeps x j-tiling -- at radius 1 and radius 2 (star13/box125, with
their 2*sweeps-deep streaming window, 5-view replicated halo, and
radius*sweeps sharded halo exchange) -- the j-tiled stream path's halo blocks
against the reference, the streaming cost model's bytes-per-point
acceptance numbers, path plumbing (autotune_engine /
sharded), the interpret=None platform default, compile_plan memoization,
and the non-divisible-block / sweeps-deeper-than-block error messages."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _x64 import x64

from repro.kernels import (autotune_engine, bytes_per_point, compile_plan,
                           get_stencil, spec_from_mask, stencil_apply,
                           stencil_ref)
from repro.kernels.stencil_engine.autotune import _fits, _step_time
from repro.kernels.stencil_engine.ops import (default_interpret,
                                              halo_block_rows)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(11)

# an asymmetric ad-hoc mask (cse plan) so the parity sweep isn't only the
# mirror-symmetric built-ins
_ASYM = np.zeros((3, 3, 3), bool)
_ASYM[1, 1, 1] = _ASYM[2, 0, 1] = _ASYM[1, 2, 2] = _ASYM[0, 1, 0] = True
ASYM_SPEC = spec_from_mask("stream-asym", _ASYM)


def _weights_for(spec, rng, integer=False):
    if integer:
        return jnp.asarray(rng.integers(1, 4, spec.w_shape), jnp.float32)
    return jnp.asarray(rng.uniform(0.1, 1.0, spec.w_shape), jnp.float32)


@pytest.mark.parametrize("name", ["stencil7", "stencil27", ASYM_SPEC])
@pytest.mark.parametrize("sweeps", [1, 2, 3])
@pytest.mark.parametrize("block_j", [None, 4])
def test_stream_matches_replicate_bit_exact_integer(name, sweeps, block_j):
    """Integer-valued f32 data makes every sum exact, so the streamed and
    replicated paths (and the reference) must agree bit-for-bit whatever
    the mask, fused-sweep depth, or j-tiling."""
    spec = get_stencil(name)
    a = jnp.asarray(RNG.integers(-4, 5, (9, 12, 16)), jnp.float32)
    w = _weights_for(spec, RNG, integer=True)
    st = stencil_apply(a, w, spec, block_i=3, block_j=block_j,
                       sweeps=sweeps, path="stream")
    rp = stencil_apply(a, w, spec, block_i=3, block_j=block_j,
                       sweeps=sweeps, path="replicate")
    np.testing.assert_array_equal(np.asarray(st), np.asarray(rp))
    np.testing.assert_array_equal(
        np.asarray(st), np.asarray(stencil_ref(a, w, spec, sweeps=sweeps)))


@pytest.mark.parametrize("name", ["stencil7", "stencil27", ASYM_SPEC])
@pytest.mark.parametrize("sweeps", [1, 2])
@pytest.mark.parametrize("block_j", [None, 4])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 4e-2)])
def test_stream_matches_replicate_float(name, sweeps, block_j, dtype, tol):
    """Float data: the two paths run the identical plan op walk, so they
    agree to (at most) per-program fma-contraction rounding."""
    spec = get_stencil(name)
    a = jnp.asarray(RNG.standard_normal((8, 12, 16)), dtype)
    w = _weights_for(spec, RNG)
    st = stencil_apply(a, w, spec, block_i=4, block_j=block_j,
                       sweeps=sweeps, path="stream")
    rp = stencil_apply(a, w, spec, block_i=4, block_j=block_j,
                       sweeps=sweeps, path="replicate")
    np.testing.assert_allclose(np.asarray(st, np.float32),
                               np.asarray(rp, np.float32),
                               rtol=tol, atol=tol)


def test_stream_f64_bit_identical_acceptance():
    """Acceptance: on the f64 reference configurations the streamed output
    is bit-identical to the replicated path and to stencil_ref -- fused
    sweeps and j-tiling included."""
    with x64():
        a = jnp.asarray(RNG.standard_normal((8, 10, 16)), jnp.float64)
        w = jnp.asarray(RNG.uniform(0.1, 1.0, (2, 2, 2)), jnp.float64)
        for sweeps in (1, 2):
            for bj in (None, 5):
                st = stencil_apply(a, w, "stencil27", block_i=4, block_j=bj,
                                   sweeps=sweeps, path="stream")
                rp = stencil_apply(a, w, "stencil27", block_i=4, block_j=bj,
                                   sweeps=sweeps, path="replicate")
                np.testing.assert_array_equal(np.asarray(st),
                                              np.asarray(rp))
                np.testing.assert_array_equal(
                    np.asarray(st),
                    np.asarray(stencil_ref(a, w, "stencil27",
                                           sweeps=sweeps)))


def test_stream_batched_and_blocking_invariance():
    """The scratch window re-primes per batch element and per j-tile: every
    (batch, blocking) combination is bit-identical on integer data."""
    a = jnp.asarray(RNG.integers(-4, 5, (2, 8, 12, 16)), jnp.float32)
    w = jnp.asarray(RNG.integers(1, 4, (2, 2, 2)), jnp.float32)
    base = stencil_apply(a, w, "stencil27", block_i=8, path="stream")
    for bi, bj in ((1, None), (2, None), (4, 6), (8, 3)):
        got = stencil_apply(a, w, "stencil27", block_i=bi, block_j=bj,
                            path="stream")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    # each batch element equals its own unbatched streamed run
    one = stencil_apply(a[0], w, "stencil27", block_i=4, path="stream")
    np.testing.assert_array_equal(np.asarray(base[0]), np.asarray(one))


def test_default_path_is_streaming():
    """path="auto" resolves to the streaming kernel whenever it fits VMEM:
    via the autotuner and for pinned blocks."""
    plan = compile_plan("stencil27")
    path, bi, bj = autotune_engine(16, 24, 128, 4, plan=plan)
    assert path == "stream" and bj is None and 16 % bi == 0
    # modeled streamed step time never exceeds replicated at equal blocks
    assert (_step_time(4, None, 24, 128, 4, 1, plan.shifts, plan.flops,
                       "stream")
            <= _step_time(4, None, 24, 128, 4, 1, plan.shifts, plan.flops,
                          "replicate"))


def test_bytes_per_point_acceptance_numbers():
    """Acceptance: the cost model charges the streamed path <= 2.5 x
    itemsize bytes/point for stencil27 at sweeps=1 (each plane read once,
    written once) where the replicated path pays for every re-fetched halo
    view; j-tiled the gap widens (4 vs 10)."""
    for itemsize in (2, 4, 8):
        assert bytes_per_point("stream", itemsize) <= 2.5 * itemsize
        assert (bytes_per_point("stream", itemsize)
                < bytes_per_point("replicate", itemsize))
        assert bytes_per_point("replicate", itemsize) == 4 * itemsize
        assert bytes_per_point("stream", itemsize, j_tiled=True) \
            == 4 * itemsize
        assert bytes_per_point("replicate", itemsize, j_tiled=True) \
            == 10 * itemsize
    # fused sweeps amortize the traffic
    assert bytes_per_point("stream", 4, sweeps=2) == 4.0
    with pytest.raises(ValueError, match="path"):
        bytes_per_point("warp", 4)


def test_autotune_engine_paths():
    plan = compile_plan("stencil27")
    # pinned paths tune blocks for that path only
    for pinned in ("stream", "replicate"):
        path, bi, bj = autotune_engine(32, 48, 128, 4, plan=plan,
                                       path=pinned)
        assert path == pinned and 32 % bi == 0
    with pytest.raises(ValueError, match="path"):
        autotune_engine(8, 8, 128, 4, plan=plan, path="warp")
    # the streaming scratch window is charged against VMEM
    assert not _fits(8, None, 288, 1024, 4, 1, 4, 8 * 1024 * 1024, "stream")
    path, bi, bj = autotune_engine(8, 288, 1024, 4, plan=plan)
    assert bj is not None and 288 % bj == 0   # VMEM wall -> j-tiled stream


def test_stream_error_messages():
    a = jnp.zeros((8, 9, 16), jnp.float32)
    w = jnp.zeros((2, 2, 2), jnp.float32)
    with pytest.raises(ValueError, match="divide M"):
        stencil_apply(a, w, "stencil27", block_i=3, path="stream")
    with pytest.raises(ValueError, match="divide N"):
        stencil_apply(a, w, "stencil27", block_i=4, block_j=4, path="stream")
    with pytest.raises(ValueError, match="block_i >= sweeps"):
        stencil_apply(a, w, "stencil27", block_i=2, sweeps=3, path="stream")
    with pytest.raises(ValueError, match="block_j >= sweeps"):
        stencil_apply(a, w, "stencil27", block_i=4, block_j=3, sweeps=4,
                      path="stream")
    with pytest.raises(ValueError, match="path"):
        stencil_apply(a, w, "stencil27", block_i=4, path="warp")


def test_interpret_none_platform_default():
    """interpret=None resolves to "interpret only without a compiled
    backend for these kernels": True on CPU/GPU hosts (the engine's VMEM
    scratch windows are Mosaic-TPU-only), False on TPU -- and the resolved
    call works."""
    assert default_interpret() == (jax.default_backend() != "tpu")
    a = jnp.asarray(RNG.standard_normal((4, 6, 16)), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.1, 1.0, (2, 2, 2)), jnp.float32)
    got = stencil_apply(a, w, "stencil27", block_i=2, interpret=None)
    ref = stencil_apply(a, w, "stencil27", block_i=2, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_compile_plan_memoized():
    """compile_plan is memoized on (spec identity, plan kind): repeated
    eager calls and equal-valued ad-hoc specs share one compiled plan, so
    un-jitted call sites and the autotuner don't rebuild the SSA schedule
    per call."""
    assert compile_plan("stencil27", "factored") is compile_plan(
        get_stencil("stencil27"), "factored")
    assert compile_plan("27") is compile_plan("stencil27")
    mask = np.zeros((3, 3, 3), bool)
    mask[1, 1, 0] = mask[1, 1, 1] = True
    s1 = spec_from_mask("memo-probe", mask)
    s2 = spec_from_mask("memo-probe", mask)
    assert s1 is not s2 and s1 == s2          # equal value, distinct objects
    assert compile_plan(s1, "cse") is compile_plan(s2, "cse")
    # distinct plan kinds stay distinct entries
    assert compile_plan("stencil27", "direct") is not compile_plan(
        "stencil27", "factored")


@pytest.mark.parametrize("name", ["star13", "box125"])
@pytest.mark.parametrize("sweeps", [1, 2])
@pytest.mark.parametrize("block_j", [None, 4])
def test_radius2_stream_matches_replicate_bit_exact_integer(name, sweeps,
                                                            block_j):
    """Acceptance: the radius-2 builtins run through both data-movement
    paths with bit-exact integer parity (and match the reference) across
    fused sweeps and j-tiling -- the streaming window now carries
    ``2 * sweeps`` halo planes and the replicated path stages 5 views."""
    spec = get_stencil(name)
    assert spec.radius == (2, 2, 2)
    a = jnp.asarray(RNG.integers(-4, 5, (12, 12, 16)), jnp.float32)
    w = _weights_for(spec, RNG, integer=True)
    st = stencil_apply(a, w, spec, block_i=4, block_j=block_j,
                       sweeps=sweeps, path="stream")
    rp = stencil_apply(a, w, spec, block_i=4, block_j=block_j,
                       sweeps=sweeps, path="replicate")
    np.testing.assert_array_equal(np.asarray(st), np.asarray(rp))
    np.testing.assert_array_equal(
        np.asarray(st), np.asarray(stencil_ref(a, w, spec, sweeps=sweeps)))


def test_radius2_stream_f64_bit_identical_acceptance():
    """Acceptance: on f64 *integer-valued* data (every reassociation exact
    within the mantissa -- the engine's cross-program parity discipline,
    see the plan IR docstring on per-program fma contraction) the radius-2
    streamed path, the replicated path, and stencil_ref are bit-identical
    across fused sweeps and j-tiling; on float f64 data the two compiled
    programs agree to per-op contraction rounding (<= ~1 ulp)."""
    with x64():
        for name in ("star13", "box125"):
            spec = get_stencil(name)
            a = jnp.asarray(RNG.integers(-4, 5, (8, 10, 16)), jnp.float64)
            w = jnp.asarray(RNG.integers(1, 4, spec.w_shape), jnp.float64)
            for sweeps in (1, 2):
                for bj in (None, 5):
                    st = stencil_apply(a, w, name, block_i=4, block_j=bj,
                                       sweeps=sweeps, path="stream")
                    rp = stencil_apply(a, w, name, block_i=4, block_j=bj,
                                       sweeps=sweeps, path="replicate")
                    np.testing.assert_array_equal(np.asarray(st),
                                                  np.asarray(rp))
                    np.testing.assert_array_equal(
                        np.asarray(st),
                        np.asarray(stencil_ref(a, w, name, sweeps=sweeps)))
            af = jnp.asarray(RNG.standard_normal((8, 10, 16)), jnp.float64)
            wf = jnp.asarray(RNG.uniform(0.1, 1.0, spec.w_shape),
                             jnp.float64)
            for sweeps in (1, 2):
                st = stencil_apply(af, wf, name, block_i=4, sweeps=sweeps,
                                   path="stream")
                rp = stencil_apply(af, wf, name, block_i=4, sweeps=sweeps,
                                   path="replicate")
                np.testing.assert_allclose(np.asarray(st), np.asarray(rp),
                                           rtol=1e-13, atol=1e-13)
                np.testing.assert_allclose(
                    np.asarray(st),
                    np.asarray(stencil_ref(af, wf, name, sweeps=sweeps)),
                    rtol=1e-13, atol=1e-13)


def test_radius2_blocking_invariance():
    """Radius-2 streaming is blocking-invariant on integer data, and the
    deep-halo validation rejects blocks thinner than radius * sweeps."""
    a = jnp.asarray(RNG.integers(-4, 5, (12, 12, 16)), jnp.float32)
    w = jnp.asarray(RNG.integers(1, 4, (3,)), jnp.float32)
    base = stencil_apply(a, w, "star13", block_i=12, path="stream")
    for bi, bj in ((2, None), (3, None), (4, 6), (6, 4)):
        got = stencil_apply(a, w, "star13", block_i=bi, block_j=bj,
                            path="stream")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    with pytest.raises(ValueError, match="block_i >= sweeps"):
        stencil_apply(a, w, "star13", block_i=3, sweeps=2)
    with pytest.raises(ValueError, match="block_j >= sweeps"):
        stencil_apply(a, w, "star13", block_i=6, block_j=3, sweeps=2)


def test_radius2_bytes_per_point_numbers():
    """The cost model stays honest at radius 2: streaming still moves
    ~2 x itemsize/point untiled while the replicated path grows to
    (2r+2) = 6 untiled and (2r+1)^2+1 = 26 j-tiled."""
    for itemsize in (2, 4, 8):
        assert bytes_per_point("stream", itemsize, radius=2) \
            == 2 * itemsize
        assert bytes_per_point("stream", itemsize, radius=2) \
            <= 2.5 * itemsize
        assert bytes_per_point("replicate", itemsize, radius=2) \
            == 6 * itemsize
        assert bytes_per_point("stream", itemsize, j_tiled=True, radius=2) \
            == 6 * itemsize
        assert bytes_per_point("replicate", itemsize, j_tiled=True,
                               radius=2) == 26 * itemsize
    # radius defaults to the plan's spec inside autotune_engine
    plan = compile_plan("star13")
    path, bi, bj = autotune_engine(16, 24, 128, 4, plan=plan)
    assert path == "stream" and 16 % bi == 0 and bi >= 2


def test_radius2_sharded_stream_two_devices_subprocess():
    """Radius-2 halo exchange: the shard_map body trades radius * sweeps
    rows per neighbour and stays bit-identical to the single-device
    streamed run -- on forced host devices."""
    code = """
        import jax, numpy as np, jax.numpy as jnp
        assert jax.device_count() == 2, jax.devices()
        from repro.kernels import stencil_apply, stencil_sharded
        from repro.sharding.planner import stencil_grid_sharding
        rng = np.random.default_rng(5)
        a = jnp.asarray(rng.integers(-4, 5, (16, 12, 16)), jnp.float32)
        mesh = jax.make_mesh((2,), ("data",))
        for name, wshape in (("star13", (3,)), ("box125", (3, 3, 3))):
            w = jnp.asarray(rng.integers(1, 4, wshape), jnp.float32)
            for s in (1, 2):
                plan = stencil_grid_sharding((16, 12, 16), mesh,
                                             axes=("data", None, None),
                                             sweeps=s, radius=2)
                assert plan.n_shards == (2, 1, 1) and plan.halo[0] == 2 * s
                st = stencil_sharded(a, w, name, mesh=mesh, sweeps=s,
                                     path="stream")
                rp = stencil_sharded(a, w, name, mesh=mesh, sweeps=s,
                                     path="replicate")
                one = stencil_apply(a, w, name, block_i=4, sweeps=s,
                                    path="stream")
                np.testing.assert_array_equal(np.asarray(st), np.asarray(rp))
                np.testing.assert_array_equal(np.asarray(st),
                                              np.asarray(one))
        print("radius2 sharded ok")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "radius2 sharded ok" in out.stdout


def test_radius2_shard_plan_halo_mismatch_raises():
    """An explicit shard_plan whose halo can't cover radius * sweeps is
    rejected with a clear message instead of silently corrupting seams."""
    from repro.sharding.planner import StencilGridPlan
    from jax.sharding import PartitionSpec as P
    a = jnp.zeros((16, 8, 16), jnp.float32)
    w = jnp.zeros((3,), jnp.float32)
    # i sharded 2 ways with a 1-plane halo; star13 needs 2
    bad = StencilGridPlan(axes=("data", None, None), n_shards=(2, 1, 1),
                          halo=(1, 2, 2), local=(8, 8, 16),
                          spec=P(None, "data", None, None), notes=[])
    from repro.kernels import stencil_sharded
    with pytest.raises(ValueError, match="halo"):
        stencil_sharded(a, w, "star13", sweeps=1, shard_plan=bad)


def test_sharded_stream_two_devices_subprocess():
    """The shard_map body streams too: 2-device halo-exchange with
    path="stream" is bit-identical to the single-device streamed run and to
    the explicit replicated sharded run -- on forced host devices."""
    code = """
        import jax, numpy as np, jax.numpy as jnp
        assert jax.device_count() == 2, jax.devices()
        from repro.kernels import stencil_apply, stencil_sharded
        rng = np.random.default_rng(5)
        a = jnp.asarray(rng.integers(-4, 5, (16, 10, 16)), jnp.float32)
        w = jnp.asarray(rng.integers(1, 4, (2, 2, 2)), jnp.float32)
        mesh = jax.make_mesh((2,), ("data",))
        for s in (1, 2):
            st = stencil_sharded(a, w, "stencil27", mesh=mesh, sweeps=s,
                                 path="stream")
            rp = stencil_sharded(a, w, "stencil27", mesh=mesh, sweeps=s,
                                 path="replicate")
            one = stencil_apply(a, w, "stencil27", block_i=4, sweeps=s,
                                path="stream")
            np.testing.assert_array_equal(np.asarray(st), np.asarray(rp))
            np.testing.assert_array_equal(np.asarray(st), np.asarray(one))
        print("stream sharded ok")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "stream sharded ok" in out.stdout


@pytest.mark.parametrize("hj,bj,itemsize,want", [
    (1, 128, 4, 8),      # stencil27, one sweep: one f32 sublane tile
    (1, 16, 4, 8),
    (4, 16, 4, 8),       # four fused sweeps still fit one tile
    (9, 32, 4, 16),      # deeper than a tile: the next multiple
    (1, 64, 2, 16),      # bf16 packs 16 rows a tile
    (9, 24, 4, 24),      # 16 does not divide 24: whole neighbour tiles
    (1, 12, 4, 12),      # 8 does not divide 12: whole neighbour tiles
    (1, 16, 2, 16),      # bf16 at bj=16: hb would be bj
    (1, 8, 4, 8),        # f32 at bj=8: hb would be bj
    (0, 16, 4, 0),       # no j halo: the tile alone
])
def test_halo_block_rows_rule(hj, bj, itemsize, want):
    """The j-halo block is the smallest sublane multiple that covers the
    halo and divides the tile, else the whole neighbour tile."""
    assert halo_block_rows(hj, bj, itemsize) == want


@pytest.mark.parametrize("name,sweeps,bj,n,dtype,bc,var,hb", [
    ("stencil27", 1, 16, 32, jnp.float32, None, False, 8),
    ("stencil27", 4, 16, 32, jnp.float32, None, False, 8),
    ("star13", 1, 16, 32, jnp.float32, None, False, 8),
    ("stencil27", 2, 16, 32, jnp.float32, "periodic", False, 8),
    ("stencil27", 2, 16, 32, jnp.float32, None, True, 8),
    ("stencil27", 1, 16, 32, jnp.bfloat16, None, False, 16),
    ("stencil27", 1, 12, 24, jnp.float32, None, False, 12),
], ids=["s27-f32", "s27-fused-s4", "star13-r2", "periodic-j", "var-coef",
        "bf16-fallback", "bj12-fallback"])
def test_jtiled_stream_halo_blocks_match_ref(name, sweeps, bj, n, dtype, bc,
                                            var, hb):
    """The j-tiled stream path stages the tile and two ``hb``-row j-halo
    blocks (whole neighbour tiles on fallback); on integer-valued data it
    equals the reference, across the periodic seam too.  bf16 rounds once,
    at the output."""
    spec = get_stencil(name)
    if var:
        spec = spec.with_coef("var")
    if bc is not None:
        spec = spec.with_bc(bc)
    hj = spec.radius[1] * sweeps * spec.sweep_apps
    assert halo_block_rows(hj, bj, jnp.dtype(dtype).itemsize) == hb
    shape = (8, n, 16)
    rng = np.random.default_rng(14)
    a = jnp.asarray(rng.integers(-4, 5, shape), jnp.float32)
    w_shape = (spec.n_weights,) + shape if var else spec.w_shape
    w = jnp.asarray(rng.integers(1, 4, w_shape), jnp.float32)
    got = stencil_apply(a.astype(dtype), w, spec, block_i=4, block_j=bj,
                        sweeps=sweeps, path="stream")
    want = stencil_ref(a, w, spec, sweeps=sweeps)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), rtol=2 ** -8, atol=0)
