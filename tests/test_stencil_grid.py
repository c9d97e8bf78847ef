"""Multi-axis (i, j, k) process-grid sharding + compute/communication overlap.

Two kinds of coverage:

* in-process 8-device tests (``@multidevice``) -- the dedicated CI leg runs
  this file under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, so
  2x2x2 and 4x2 meshes execute real shard_map programs with per-axis
  ppermute exchanges, overlap on and off, bit-exact against the
  single-device oracle on integer-valued data (corner/edge ghosts are where
  a diagonal-heavy stencil27 goes wrong if the transitive j -> k -> i
  exchange mis-fills anything);
* subprocess + pure-planner tests that run on every leg: the thin-shard
  validation raise, plan fallbacks, the per-axis exchange-bytes model, and
  one small end-to-end 2x2x2 parity check so tier-1 keeps multi-axis
  coverage even where the in-process leg is absent.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import stencil_apply, stencil_sharded
from repro.kernels.stencil_engine import exchange_bytes_per_point, get_stencil

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 in-process devices (the multidevice CI leg sets "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _run(code: str, n_dev: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def _field(shape, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(-4, 5, size=shape), dtype)


def _weights(spec_name, seed=1, dtype=jnp.float32):
    spec = get_stencil(spec_name)
    rng = np.random.default_rng(seed)
    shape = {"stencil27": (2, 2, 2), "star13": (3,), "stencil7": (4,),
             "box125": (3, 3, 3)}[spec.name.split("_")[0]
                                  if "_" in spec.name else spec.name]
    return jnp.asarray(rng.integers(-3, 4, size=shape), dtype)


# ---------------------------------------------------------------------------
# planner validation (no devices needed beyond what the process has)
# ---------------------------------------------------------------------------

THIN_SHARD = {
    # the 1-D i-chain: 16 rows / 8 shards = 2 local rows < radius 1 x
    # sweeps 4; an indivisible extent still falls back with a PlanNote
    "i-axis": """
        import jax
        from repro.sharding import stencil_grid_sharding
        mesh = jax.make_mesh((8,), ("data",))
        axes = ("data", None, None)
        try:
            stencil_grid_sharding((16, 8, 8), mesh, axes=axes, sweeps=4,
                                  radius=1)
        except ValueError as e:
            msg = str(e)
            assert "i-extent 16" in msg and "'data'=8" in msg
            assert "2 local" in msg and "radius * sweeps" in msg
        else:
            raise AssertionError("thin shard did not raise")
        plan = stencil_grid_sharding((17, 8, 8), mesh, axes=axes, sweeps=1,
                                     radius=1)
        assert plan.n_shards == (1, 1, 1) and plan.notes
        print("thin-shard raise ok")
    """,
    # a 2x2x2 grid too thin on j; size-1 / indivisible axes still fall
    # back with a PlanNote
    "grid-axis": """
        import jax
        from repro.sharding import stencil_grid_sharding
        mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"))
        try:
            stencil_grid_sharding((16, 4, 16), mesh, axes=("x", "y", "z"),
                                  sweeps=3, radius=1)
        except ValueError as e:
            msg = str(e)
            assert "j-extent 4" in msg and "'y'=2" in msg
        else:
            raise AssertionError("thin grid shard did not raise")
        plan = stencil_grid_sharding((16, 9, 16), mesh, axes=("x", "y", "z"),
                                     sweeps=1, radius=1)
        assert plan.axes == ("x", None, "z")
        assert plan.n_shards == (2, 1, 2)
        assert any("not divisible" in n.reason for n in plan.notes)
        print("thin-shard raise ok")
    """,
}


@pytest.mark.parametrize("case", sorted(THIN_SHARD))
def test_thin_shard_raises_subprocess(case):
    """A mesh axis too wide for its extent raises with the shapes in the
    message instead of silently planning a halo the shards cannot cover;
    the graceful fallbacks stay graceful."""
    assert "thin-shard raise ok" in _run(THIN_SHARD[case])


def test_grid_plan_spec_and_locals():
    """Pure-planner shape arithmetic on a fabricated mesh via subprocess-free
    checks where possible: the exchange-bytes model is deterministic."""
    # j and k faces grow transitively: k slabs carry j ghosts, i slabs both
    b = exchange_bytes_per_point(4, (2, 1, 1), (8, 8, 16), sweeps=1)
    assert b["j"] == 2 * 1 * 8 * 16 * 4 / (8 * 8 * 16)
    assert b["k"] == 2 * 1 * 8 * (8 + 2) * 4 / (8 * 8 * 16)
    assert b["i"] == 2 * 2 * (8 + 2) * (16 + 2) * 4 / (8 * 8 * 16)
    assert b["total"] == pytest.approx(b["i"] + b["j"] + b["k"])
    # unsharded axes cost nothing; sweeps amortize the deep exchange
    assert exchange_bytes_per_point(4, (0, 0, 0), (8, 8, 16))["total"] == 0
    assert exchange_bytes_per_point(4, (2, 0, 0), (8, 8, 16), sweeps=2)[
        "i"] == exchange_bytes_per_point(4, (2, 0, 0), (8, 8, 16))["i"] / 2
    # var coef ships n_weights coefficient slabs with the field
    assert exchange_bytes_per_point(4, (1, 0, 0), (8, 8, 16), n_weights=3)[
        "i"] == 4 * exchange_bytes_per_point(4, (1, 0, 0), (8, 8, 16))["i"]


def test_multi_axis_needs_explicit_mesh():
    a = jnp.zeros((8, 8, 16), jnp.float32)
    w = jnp.zeros((2, 2, 2), jnp.float32)
    with pytest.raises(ValueError, match="explicit mesh"):
        stencil_sharded(a, w, "stencil27", axes=("x", "y", None))


def test_overlap_rejects_wavefront_mode():
    a = jnp.zeros((8, 8, 16), jnp.float32)
    w = jnp.zeros((3,), jnp.float32)
    with pytest.raises(ValueError, match="overlap"):
        stencil_sharded(a, w, "star13", mode="wavefront", overlap="on")


def test_unknown_overlap_rejected():
    a = jnp.zeros((8, 8, 16), jnp.float32)
    w = jnp.zeros((2, 2, 2), jnp.float32)
    with pytest.raises(ValueError, match="overlap"):
        stencil_sharded(a, w, "stencil27", overlap="maybe")


def test_grid_2x2x2_parity_subprocess():
    """One small end-to-end 3-D grid parity check that runs on every leg
    (the in-process @multidevice matrix below is the thorough version)."""
    print(_run("""
        import jax, numpy as np, jax.numpy as jnp
        assert jax.device_count() == 8, jax.devices()
        from repro.kernels import stencil_apply, stencil_sharded
        rng = np.random.default_rng(3)
        a = jnp.asarray(rng.integers(-4, 5, (8, 8, 16)), jnp.float32)
        w = jnp.asarray(rng.integers(-3, 4, (2, 2, 2)), jnp.float32)
        mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"))
        ref = stencil_apply(a, w, "stencil27", sweeps=2)
        for overlap in ("off", "on"):
            got = stencil_sharded(a, w, "stencil27", mesh=mesh,
                                  axes=("x", "y", "z"), sweeps=2,
                                  overlap=overlap)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        print("grid 2x2x2 ok")
    """))


# ---------------------------------------------------------------------------
# in-process 8-device matrix (the dedicated multidevice CI leg)
# ---------------------------------------------------------------------------

@multidevice
@pytest.mark.parametrize("bc", [None, "periodic"])
@pytest.mark.parametrize("name,shape", [("stencil27", (8, 8, 16)),
                                        ("star13", (16, 16, 16))])
@pytest.mark.parametrize("path", ["stream", "replicate"])
@pytest.mark.parametrize("overlap", ["off", "on"])
def test_grid_3d_bitexact_vs_oracle(bc, name, shape, path, overlap):
    """Corner/edge ghost correctness: a (2,2,2)-sharded run is bit-exact vs
    the single-device oracle on integer data -- BC x radius {1 (the
    diagonal-heavy stencil27, where wrong corners change the answer),
    2 (star13)} x path x overlap."""
    a = _field(shape, seed=7)
    w = _weights(name, seed=8)
    mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"))
    ref = stencil_apply(a, w, name, bc=bc, sweeps=2)
    got = stencil_sharded(a, w, name, mesh=mesh, axes=("x", "y", "z"),
                          bc=bc, sweeps=2, path=path, overlap=overlap)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@multidevice
@pytest.mark.parametrize("overlap", ["off", "on"])
def test_grid_4x2_redblack(overlap):
    """A 4x2 (i, j) grid with the red-black ordering: sweep_apps == 2
    doubles every axis's deep halo and the global checkerboard parity must
    stay aligned across both kinds of shard seams."""
    a = _field((16, 8, 16), seed=9)
    w = _weights("stencil7", seed=10)
    mesh = jax.make_mesh((4, 2), ("x", "y"))
    ref = stencil_apply(a, w, "stencil7_redblack", sweeps=2)
    got = stencil_sharded(a, w, "stencil7_redblack", mesh=mesh,
                          axes=("x", "y", None), sweeps=2, overlap=overlap)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@multidevice
@pytest.mark.parametrize("overlap", ["off", "on"])
def test_grid_var_coef(overlap):
    """Variable-coefficient planes ride the same per-axis exchanges as the
    field (the strip kernel consumes a pre-extended coefficient strip)."""
    spec = get_stencil("stencil27").with_coef("var")
    a = _field((8, 8, 16), seed=11)
    rng = np.random.default_rng(12)
    w = jnp.asarray(rng.integers(-3, 4, (spec.n_weights, 8, 8, 16)),
                    jnp.float32)
    mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"))
    ref = stencil_apply(a, w, spec, sweeps=2)
    got = stencil_sharded(a, w, spec, mesh=mesh, axes=("x", "y", "z"),
                          sweeps=2, overlap=overlap)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@multidevice
@pytest.mark.parametrize("bc", [None, "periodic"])
def test_grid_wavefront_mode(bc):
    """The temporal-wavefront pipeline on a 3-D grid: the serialized
    multi-axis deep-halo exchange feeds the pipeline's pre-extended slab."""
    a = _field((16, 24, 16), seed=13)
    w = _weights("star13", seed=14)
    mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"))
    ref = stencil_apply(a, w, "star13", bc=bc, sweeps=3)
    got = stencil_sharded(a, w, "star13", mesh=mesh, axes=("x", "y", "z"),
                          bc=bc, sweeps=3, mode="wavefront")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@multidevice
def test_grid_batched_and_neumann():
    a = _field((2, 16, 8, 16), seed=15)
    w = _weights("stencil27", seed=16)
    mesh = jax.make_mesh((4, 2), ("x", "y"))
    ref = stencil_apply(a, w, "stencil27", bc="neumann", sweeps=2)
    for overlap in ("off", "on"):
        got = stencil_sharded(a, w, "stencil27", mesh=mesh,
                              axes=("x", "y", None), bc="neumann", sweeps=2,
                              overlap=overlap)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@multidevice
def test_grid_block_j_rejected_when_j_sharded():
    a = _field((8, 8, 16), seed=17)
    w = _weights("stencil27", seed=18)
    mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"))
    with pytest.raises(ValueError, match="block_j"):
        stencil_sharded(a, w, "stencil27", mesh=mesh, axes=("x", "y", "z"),
                        block_j=4)


@multidevice
def test_grid_overlap_quietly_serializes_when_i_unsharded():
    """overlap='on' with an unsharded i axis has nothing to hide -- the
    call still runs (serialized) and stays exact."""
    a = _field((8, 8, 16), seed=19)
    w = _weights("stencil27", seed=20)
    mesh = jax.make_mesh((2, 2), ("y", "z"))
    ref = stencil_apply(a, w, "stencil27", sweeps=2)
    got = stencil_sharded(a, w, "stencil27", mesh=mesh,
                          axes=(None, "y", "z"), sweeps=2, overlap="on")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@multidevice
def test_grid_corrupt_halo_per_axis_detected_and_recovered():
    """CorruptHalo with an axes filter hits exactly one face's exchange;
    the guard detects it and the ladder recovers off the sharded path."""
    from repro.kernels.stencil_engine import CorruptHalo, inject
    from repro.kernels.stencil_engine import last_guard_report
    a = _field((8, 8, 16), seed=21)
    w = jnp.asarray(np.random.default_rng(22).integers(1, 4, (2, 2, 2)),
                    jnp.float32)
    mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"))
    ref = stencil_apply(a, w, "stencil27", sweeps=2)
    for axis in ("i", "j", "k"):
        with inject(CorruptHalo(seed=3, mode="garbage", axes=(axis,))):
            got = stencil_sharded(a, w, "stencil27", mesh=mesh,
                                  axes=("x", "y", "z"), sweeps=2,
                                  guard="full")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        rep = last_guard_report().describe()["guard"]
        assert rep["demotions"], (axis, rep)


@multidevice
def test_grid_corrupt_unsharded_axis_is_harmless():
    """A fault filtered to an axis that never exchanges cannot fire: the
    sharded run stays clean with no guard at all."""
    from repro.kernels.stencil_engine import CorruptHalo, inject
    a = _field((16, 8, 16), seed=23)
    w = _weights("stencil27", seed=24)
    mesh = jax.make_mesh((4, 2), ("x", "y"))
    ref = stencil_apply(a, w, "stencil27", sweeps=2)
    with inject(CorruptHalo(seed=3, mode="nan", axes=("k",))):
        got = stencil_sharded(a, w, "stencil27", mesh=mesh,
                              axes=("x", "y", None), sweeps=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
