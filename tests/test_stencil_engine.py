"""Unified stencil engine: registry, parity, batching, fused sweeps,
autotuning, and 2-device halo-exchange sharding (subprocess)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _x64 import x64

from repro.kernels import (compile_plan, get_stencil, list_stencils,
                           spec_from_mask, stencil_apply, stencil_ref,
                           stencil3_ref, stencil7_ref, stencil27_ref)
from repro.kernels.stencil_engine.autotune import (autotune_block_i,
                                                   autotune_blocks,
                                                   pick_block_i,
                                                   pick_block_rows)
from repro.kernels.stencil_engine.plan import mirror_symmetric

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(7)


def _naive27(a, w):
    """Independent numpy oracle (not engine-backed)."""
    a = np.asarray(a, np.float64)
    w = np.asarray(w, np.float64)
    out = np.zeros_like(a)
    for i in range(1, a.shape[0] - 1):
        for j in range(1, a.shape[1] - 1):
            for k in range(1, a.shape[2] - 1):
                s = 0.0
                for di in (-1, 0, 1):
                    for dj in (-1, 0, 1):
                        for dk in (-1, 0, 1):
                            s += (w[abs(di), abs(dj), abs(dk)]
                                  * a[i + di, j + dj, k + dk])
                out[i, j, k] = s
    return out


def test_registry_names_and_aliases():
    assert get_stencil("stencil27") is get_stencil("27")
    assert get_stencil(27).taps == 27
    assert get_stencil("stencil7").taps == 7
    assert get_stencil("stencil3").taps == 3
    assert {"stencil3", "stencil7", "stencil27"} <= set(list_stencils())
    with pytest.raises(KeyError):
        get_stencil("stencil99")


def test_engine_matches_independent_oracle():
    """Non-circular check: the engine against a hand-rolled numpy loop."""
    a = jnp.asarray(RNG.standard_normal((6, 7, 9)), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.1, 1.0, (2, 2, 2)), jnp.float32)
    got = stencil_apply(a, w, "stencil27", block_i=3)
    np.testing.assert_allclose(np.asarray(got, np.float64), _naive27(a, w),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name,wshape", [("stencil7", (4,)),
                                         ("stencil27", (2, 2, 2))])
@pytest.mark.parametrize("shape,bi", [((8, 16, 32), 4),   # even everywhere
                                      ((9, 11, 17), 3),   # odd everywhere
                                      ((10, 8, 24), 5)])  # mixed
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_volumetric_parity_sizes_dtypes(name, wshape, shape, bi, dtype):
    a = jnp.asarray(RNG.standard_normal(shape), dtype)
    w = jnp.asarray(RNG.uniform(0.1, 1.0, wshape), jnp.float32)
    got = stencil_apply(a, w, name, block_i=bi)
    ref = stencil_ref(a.astype(jnp.float32), w, name).astype(dtype)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_f64_bit_for_bit_parity():
    """In f64 the kernel and the refs agree exactly (same tap order, same
    arithmetic) -- the engine's reference path."""
    with x64():
        a = jnp.asarray(RNG.standard_normal((8, 10, 16)), jnp.float64)
        a2 = jnp.asarray(RNG.standard_normal((6, 32)), jnp.float64)
        w27 = jnp.asarray(RNG.uniform(0.1, 1.0, (2, 2, 2)), jnp.float64)
        w7 = jnp.asarray(RNG.uniform(0.1, 1.0, 4), jnp.float64)
        w3 = jnp.asarray(RNG.uniform(0.1, 1.0, 2), jnp.float64)
        np.testing.assert_array_equal(
            np.asarray(stencil_apply(a, w27, "stencil27", block_i=4)),
            np.asarray(stencil27_ref(a, w27)))
        np.testing.assert_array_equal(
            np.asarray(stencil_apply(a, w7, "stencil7", block_i=2)),
            np.asarray(stencil7_ref(a, w7)))
        np.testing.assert_array_equal(
            np.asarray(stencil_apply(a2, w3, "stencil3", block_i=3)),
            np.asarray(stencil3_ref(a2, w3)))
        # fused sweeps stay bit-exact too
        np.testing.assert_array_equal(
            np.asarray(stencil_apply(a, w27, "stencil27", block_i=4,
                                     sweeps=3)),
            np.asarray(stencil_ref(a, w27, "stencil27", sweeps=3)))


@pytest.mark.parametrize("batch", [(2,), (2, 3)])
def test_batched_execution(batch):
    shape = batch + (8, 10, 16)
    a = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.1, 1.0, (2, 2, 2)), jnp.float32)
    got = stencil_apply(a, w, "stencil27", block_i=4)
    ref = stencil_ref(a, w, "stencil27")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # every batch element equals its own unbatched run
    one = stencil_apply(a.reshape(-1, 8, 10, 16)[0], w, "stencil27",
                        block_i=4)
    np.testing.assert_array_equal(
        np.asarray(got.reshape(-1, 8, 10, 16)[0]), np.asarray(one))


@pytest.mark.parametrize("sweeps", [1, 2, 3])
@pytest.mark.parametrize("name", ["stencil3", "stencil7", "stencil27"])
def test_fused_sweeps_match_iterated(name, sweeps):
    spec = get_stencil(name)
    if spec.ndim == 1:
        a = jnp.asarray(RNG.standard_normal((8, 32)), jnp.float32)
        w = jnp.asarray(RNG.uniform(0.1, 1.0, 2), jnp.float32)
        bi = 4
    else:
        a = jnp.asarray(RNG.standard_normal((8, 10, 16)), jnp.float32)
        w = jnp.asarray(RNG.uniform(0.1, 1.0, spec.w_shape), jnp.float32)
        bi = 4
    fused = stencil_apply(a, w, name, block_i=bi, sweeps=sweeps)
    it = a
    for _ in range(sweeps):
        it = stencil_apply(it, w, name, block_i=bi)
    # f32: up to FMA-contraction noise between the two compiled programs
    # (the f64 path is asserted bit-exact in test_f64_bit_for_bit_parity)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(it),
                               rtol=1e-6, atol=1e-6)
    ref = stencil_ref(a, w, name, sweeps=sweeps)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sweeps_deeper_than_block_halo_raises():
    a = jnp.zeros((8, 8, 16), jnp.float32)
    w = jnp.zeros((2, 2, 2), jnp.float32)
    with pytest.raises(ValueError, match="halo"):
        stencil_apply(a, w, "stencil27", block_i=2, sweeps=3)


def test_custom_mask_spec():
    """An ad-hoc mask (i-axis-only 3-point) runs through the same engine."""
    mask = -np.ones((3, 3, 3), np.int64)
    mask[0, 1, 1] = 0          # (di=-1) -> w[0]
    mask[1, 1, 1] = 1          # centre  -> w[1]
    mask[2, 1, 1] = 0          # (di=+1) -> w[0]
    spec = spec_from_mask("i3", mask)
    assert spec.taps == 3 and spec.n_weights == 2
    a = jnp.asarray(RNG.standard_normal((8, 6, 16)), jnp.float32)
    w = jnp.asarray([0.25, 0.5], jnp.float32)
    got = stencil_apply(a, w, spec, block_i=4)
    ref = stencil_ref(a, w, spec)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # hand-check one interior point
    i, j, k = 3, 2, 5
    expect = float(0.25 * a[i - 1, j, k] + 0.5 * a[i, j, k]
                   + 0.25 * a[i + 1, j, k])
    assert abs(float(got[i, j, k]) - expect) < 1e-5


def test_boolean_mask_assigns_unique_weights():
    mask = np.zeros((3, 3, 3), bool)
    mask[1, 1, 0] = mask[1, 1, 1] = mask[1, 1, 2] = True
    spec = spec_from_mask("k3-unsym", mask)
    assert spec.n_weights == 3
    a = jnp.asarray(RNG.standard_normal((4, 6, 16)), jnp.float32)
    w = jnp.asarray([1.0, 2.0, 3.0], jnp.float32)
    got = stencil_apply(a, w, spec, block_i=2)
    i, j, k = 2, 3, 7
    expect = float(1.0 * a[i, j, k - 1] + 2.0 * a[i, j, k]
                   + 3.0 * a[i, j, k + 1])
    assert abs(float(got[i, j, k]) - expect) < 1e-5


def test_plan_op_counts_factored_vs_direct():
    """Acceptance: the stencil27 factored plan is <= 1/3 of the direct
    plan's shifts and <= 40% of its flops, statically, via the plan IR."""
    direct = compile_plan("stencil27", "direct")
    factored = compile_plan("stencil27", "factored")
    cse = compile_plan("stencil27", "cse")
    assert (direct.shifts, direct.flops) == (54, 53)   # 27 muls + 26 adds
    assert factored.shifts * 3 <= direct.shifts
    assert factored.flops <= 0.4 * direct.flops
    assert cse.shifts < direct.shifts and cse.flops == direct.flops
    # auto selects the modeled-fastest (kind, unroll) -- the chosen variant
    # is never modeled-slower than any explicit kind (factored stays in the
    # candidate set for the symmetric built-ins, cse otherwise)
    for name in ("stencil3", "stencil7", "stencil27"):
        assert mirror_symmetric(get_stencil(name))
        auto = compile_plan(name, "auto")
        assert auto.kind in ("cse", "factored")
        for kind in ("direct", "cse", "factored"):
            explicit = compile_plan(name, kind)
            assert (auto.modeled.cycles_per_point
                    <= explicit.modeled.cycles_per_point + 1e-9)
    mask = np.zeros((3, 3, 3), bool)
    mask[1, 1, 1] = mask[1, 1, 2] = True               # no -k mirror tap
    lop = spec_from_mask("lop", mask)
    assert not mirror_symmetric(lop)
    assert compile_plan(lop, "auto").kind == "cse"
    with pytest.raises(ValueError, match="mirror-symmetric"):
        compile_plan(lop, "factored")


def test_plan_kinds_agree_and_match_ref():
    """Every plan kind is bit-identical to the same-plan reference; across
    plan kinds the reassociated sums agree to f32 rounding."""
    a = jnp.asarray(RNG.standard_normal((8, 12, 16)), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.1, 1.0, (2, 2, 2)), jnp.float32)
    outs = {}
    for plan in ("direct", "cse", "factored"):
        got = stencil_apply(a, w, "stencil27", block_i=4, plan=plan)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(stencil_ref(a, w, "stencil27",
                                                    plan=plan)))
        outs[plan] = np.asarray(got)
    for plan in ("cse", "factored"):
        np.testing.assert_allclose(outs[plan], outs["direct"],
                                   rtol=1e-5, atol=1e-5)


def test_factored_f64_bit_identical_to_ref():
    """Acceptance: stencil27 factored, f64, bit-identical to stencil_ref --
    blocked kernel vs full-array oracle, fused sweeps included."""
    with x64():
        a = jnp.asarray(RNG.standard_normal((8, 10, 16)), jnp.float64)
        w = jnp.asarray(RNG.uniform(0.1, 1.0, (2, 2, 2)), jnp.float64)
        for sweeps in (1, 2):
            got = stencil_apply(a, w, "stencil27", block_i=4,
                                plan="factored", sweeps=sweeps)
            ref = stencil_ref(a, w, "stencil27", sweeps=sweeps,
                              plan="factored")
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("name", ["stencil7", "stencil27"])
@pytest.mark.parametrize("sweeps", [1, 2])
def test_j_tiled_matches_untiled(name, sweeps):
    """j-tiling is pure data movement: on integer-valued data (exact
    arithmetic, immune to per-program fma contraction) every blocking is
    bit-identical to the untiled run and the reference; on float data it
    agrees to rounding."""
    spec = get_stencil(name)
    ai = jnp.asarray(RNG.integers(-4, 5, (8, 12, 16)), jnp.float32)
    wi = jnp.asarray(RNG.integers(1, 4, spec.w_shape), jnp.float32)
    untiled = stencil_apply(ai, wi, name, block_i=4, sweeps=sweeps)
    for bj in (3, 4, 6):
        tiled = stencil_apply(ai, wi, name, block_i=4, block_j=bj,
                              sweeps=sweeps)
        np.testing.assert_array_equal(np.asarray(tiled), np.asarray(untiled))
    np.testing.assert_array_equal(
        np.asarray(untiled),
        np.asarray(stencil_ref(ai, wi, name, sweeps=sweeps)))
    af = jnp.asarray(RNG.standard_normal((8, 12, 16)), jnp.float32)
    wf = jnp.asarray(RNG.uniform(0.1, 1.0, spec.w_shape), jnp.float32)
    uf = stencil_apply(af, wf, name, block_i=4, sweeps=sweeps)
    tf = stencil_apply(af, wf, name, block_i=4, block_j=4, sweeps=sweeps)
    np.testing.assert_allclose(np.asarray(tf), np.asarray(uf),
                               rtol=1e-6, atol=1e-6)


def test_j_tiled_batched_and_custom_mask():
    ab = jnp.asarray(RNG.integers(-4, 5, (2, 6, 9, 16)), jnp.float32)
    w = jnp.asarray(RNG.integers(1, 4, (2, 2, 2)), jnp.float32)
    got = stencil_apply(ab, w, "stencil27", block_i=3, block_j=3)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(stencil_apply(ab, w, "stencil27", block_i=3)))
    mask = np.zeros((3, 3, 3), bool)                  # asymmetric: cse plan
    mask[1, 1, 1] = mask[2, 0, 1] = mask[1, 2, 2] = True
    spec = spec_from_mask("jt-asym", mask)
    wc = jnp.asarray([1.0, -2.0, 2.0], jnp.float32)
    a = ab[0]
    np.testing.assert_array_equal(
        np.asarray(stencil_apply(a, wc, spec, block_i=2, block_j=3)),
        np.asarray(stencil_ref(a, wc, spec)))


def test_j_tiled_sweeps_deeper_than_halo_raises():
    a = jnp.zeros((8, 8, 16), jnp.float32)
    w = jnp.zeros((2, 2, 2), jnp.float32)
    with pytest.raises(ValueError, match="block_j"):
        stencil_apply(a, w, "stencil27", block_i=4, block_j=2, sweeps=3)


def test_autotune_blocks_engages_j_tiling_past_vmem_wall():
    """When no full-N block fits the budget (previously a hard wall), the
    tuner returns a feasible (bi, bj) tile instead."""
    plan = compile_plan("stencil27")
    # comfortable slab: stays untiled
    bi, bj = autotune_blocks(32, 48, 128, 4, plan=plan)
    assert bj is None and 32 % bi == 0
    # N x P slab over budget even at bi=1: j-tiling kicks in
    bi, bj = autotune_blocks(8, 288, 1024, 4, plan=plan)
    assert bj is not None and 288 % bj == 0 and 8 % bi == 0
    from repro.kernels.stencil_engine.autotune import _fits
    from repro.kernels.stencil_engine.common import DEFAULT_VMEM_BUDGET
    assert _fits(bi, bj, 288, 1024, 4, 1, 4, DEFAULT_VMEM_BUDGET)
    assert not _fits(1, None, 288, 1024, 4, 1, 4, DEFAULT_VMEM_BUDGET)
    # the plan-aware model charges the factored schedule ~4x less VPU work
    direct = compile_plan("stencil27", "direct")
    from repro.kernels.stencil_engine.autotune import _step_time
    assert (_step_time(8, None, 48, 128, 4, 1, plan.shifts, plan.flops)
            <= _step_time(8, None, 48, 128, 4, 1, direct.shifts,
                          direct.flops))


def test_pick_block_rows_divisor_fallback():
    # power-of-two path unchanged
    assert pick_block_rows(256, 128, 4) == 256
    # rows=12: no power-of-two candidate divides it; the old code returned
    # all 12 rows even when that blew the budget -- now the largest fitting
    # divisor wins
    assert pick_block_rows(12, 1024, 4, vmem_budget=16 * 1024) == 4
    # and when the full tile fits, behaviour is unchanged (rows itself)
    assert pick_block_rows(12, 16, 4) == 12
    # nothing fits: degrade to single rows, never over budget by choice
    assert pick_block_rows(7, 4096, 8, vmem_budget=1024) == 1


def test_sharded_fn_cache_keyed_on_device_ids_and_bounded():
    """The shard_map program cache must not key on Mesh object identity
    (leaking meshes) and must stay bounded."""
    from jax.sharding import PartitionSpec as P
    from repro.kernels.stencil_engine import sharded as sh
    plan = compile_plan("stencil27")
    part = P(None, "data")
    from jax.sharding import Mesh
    m1 = jax.make_mesh((1,), ("data",))
    m2 = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    assert sh._mesh_key(m1) == sh._mesh_key(m2)
    f1 = sh._sharded_fn(plan, m1, "data", 4, None, 1, True, 1, 8, 1, 8, part)
    f2 = sh._sharded_fn(plan, m2, "data", 4, None, 1, True, 1, 8, 1, 8, part)
    assert f1 is f2
    for k in range(sh._SHARDED_CACHE_MAX + 8):
        sh._sharded_fn(plan, m1, "data", 4, None, 1, True, 1, 8 + k, 1,
                       8 + k, part)
    assert len(sh._SHARDED_CACHE) <= sh._SHARDED_CACHE_MAX


def test_autotuner_properties():
    for m, n, p, s in [(32, 48, 128, 1), (30, 30, 30, 2), (16, 8, 128, 3)]:
        bi = autotune_block_i(m, n, p, 4, sweeps=s)
        assert m % bi == 0 and bi >= s, (m, bi, s)
    # legacy alias keeps its contract (divisor, fits the budget reasoning)
    assert 32 % pick_block_i(32, 48, 128, 4) == 0
    # huge planes fall back to small feasible blocks rather than exploding
    bi = autotune_block_i(1024, 512, 512, 4)
    assert 1024 % bi == 0


def test_planner_fallbacks_and_plan():
    from repro.sharding.planner import stencil_halo_sharding
    mesh = jax.make_mesh((1,), ("data",))
    plan = stencil_halo_sharding(16, mesh, sweeps=1)
    assert plan.n_shards == 1                      # 1 device: unsharded
    assert any("unsharded" in n.reason for n in plan.notes)


def test_sharded_two_devices_subprocess():
    """2-device shard_map halo-exchange == single-device engine, bit-exact,
    for s in {1, 2} -- on forced host-platform devices."""
    code = """
        import jax, numpy as np, jax.numpy as jnp
        assert jax.device_count() == 2, jax.devices()
        from repro.kernels import stencil_apply, stencil_ref, stencil_sharded
        from repro.sharding.planner import stencil_halo_sharding
        rng = np.random.default_rng(3)
        a = jnp.asarray(rng.standard_normal((16, 10, 16)), jnp.float32)
        w = jnp.asarray(rng.uniform(0.1, 1.0, (2, 2, 2)), jnp.float32)
        mesh = jax.make_mesh((2,), ("data",))
        for s in (1, 2):
            plan = stencil_halo_sharding(16, mesh, sweeps=s)
            assert plan.n_shards == 2 and plan.halo == s
            got = stencil_sharded(a, w, "stencil27", mesh=mesh, sweeps=s)
            one = stencil_apply(a, w, "stencil27", block_i=4, sweeps=s)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(one))
            ref = stencil_ref(a, w, "stencil27", sweeps=s)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)
        # batched + sharded
        ab = jnp.asarray(rng.standard_normal((2, 16, 8, 16)), jnp.float32)
        got = stencil_sharded(ab, w, "stencil27", mesh=mesh, sweeps=2)
        one = stencil_apply(ab, w, "stencil27", block_i=4, sweeps=2)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(one))
        print("sharded ok")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "sharded ok" in out.stdout
