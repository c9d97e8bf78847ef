"""Plan-correctness properties: factored / cse / direct schedules agree --
bit-identically in f64 (integer-valued data makes every reassociation exact),
to tolerance in f32/bf16 -- across random ``spec_from_mask`` masks, fused
sweeps, and j-tiled vs untiled blockings (hypothesis, stub fallback)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:  # optional dev dep -- property tests skip, rest runs
    from _hypothesis_stub import given, settings, st  # noqa: F401

from _x64 import x64

from repro.kernels import (compile_plan, spec_from_mask, stencil_apply,
                           stencil_ref)
from repro.kernels.stencil_engine.plan import mirror_symmetric

ORBITS = [(a, b, c) for a in (0, 1) for b in (0, 1) for c in (0, 1)]


def _symmetric_mask(rng) -> np.ndarray:
    """Random mirror-symmetric coefficient-index mask: a nonempty union of
    sign-flip orbits of |offset| classes, one shared weight per orbit."""
    keep = [o for o in ORBITS if rng.random() < 0.6]
    if not keep:
        keep = [ORBITS[rng.integers(len(ORBITS))]]
    m = -np.ones((3, 3, 3), np.int64)
    for idx, (a, b, c) in enumerate(keep):
        for di in ({-a, a}):
            for dj in ({-b, b}):
                for dk in ({-c, c}):
                    m[di + 1, dj + 1, dk + 1] = idx
    return m


def _arbitrary_mask(rng) -> np.ndarray:
    m = rng.random((3, 3, 3)) < 0.4
    if not m.any():
        m[1, 1, 1] = True
    return m


def _plans_for(spec):
    plans = ["direct", "cse"]
    if mirror_symmetric(spec):
        plans.append("factored")
    return plans


def check_plans_agree(seed: int, sweeps: int, block_j, symmetric: bool):
    """The property body (also exercised by the fixed-seed smoke test)."""
    rng = np.random.default_rng(seed)
    mask = _symmetric_mask(rng) if symmetric else _arbitrary_mask(rng)
    spec = spec_from_mask(f"prop-{'s' if symmetric else 'a'}{seed}", mask)
    if symmetric:
        assert mirror_symmetric(spec)
    plans = _plans_for(spec)
    shape = (6, 8, 16)

    # f64 + integer-valued data: every sum is exact, so reassociated plans
    # (and any blocking) must agree bit-for-bit.
    with x64():
        a = jnp.asarray(rng.integers(-4, 5, shape), jnp.float64)
        w = jnp.asarray(rng.integers(1, 4, spec.n_weights), jnp.float64)
        outs = [np.asarray(stencil_apply(a, w, spec, block_i=3,
                                         block_j=block_j, plan=p,
                                         sweeps=sweeps))
                for p in plans]
        ref = np.asarray(stencil_ref(a, w, spec, sweeps=sweeps,
                                     plan="direct"))
        for got in outs:
            np.testing.assert_array_equal(got, ref)

    # f32 / bf16 float data: reassociation agrees to rounding.
    for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 4e-2)):
        af = jnp.asarray(rng.standard_normal(shape), dtype)
        wf = jnp.asarray(rng.uniform(0.1, 1.0, spec.n_weights), jnp.float32)
        base = None
        for p in plans:
            got = np.asarray(stencil_apply(af, wf, spec, block_i=3,
                                           block_j=block_j, plan=p,
                                           sweeps=sweeps), np.float32)
            if base is None:
                base = got
            else:
                np.testing.assert_allclose(got, base, rtol=tol, atol=tol)


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 2),
       st.sampled_from([None, 4]), st.booleans())
def test_plans_agree_property(seed, sweeps, block_j, symmetric):
    check_plans_agree(seed, sweeps, block_j, symmetric)


@pytest.mark.parametrize("seed,sweeps,block_j,symmetric", [
    (7, 1, None, True),
    (7, 2, 4, True),
    (11, 1, 4, False),
    (23, 2, None, False),
])
def test_plans_agree_fixed_examples(seed, sweeps, block_j, symmetric):
    """Deterministic instances of the property -- run even without
    hypothesis installed."""
    check_plans_agree(seed, sweeps, block_j, symmetric)


def test_plan_shift_counts_never_exceed_direct():
    """cse/factored are optimizations: for random masks they never emit more
    shifts than the naive schedule, and flops never grow."""
    rng = np.random.default_rng(0)
    for k in range(20):
        sym = k % 2 == 0
        mask = _symmetric_mask(rng) if sym else _arbitrary_mask(rng)
        spec = spec_from_mask(f"cnt{k}", mask)
        direct = compile_plan(spec, "direct")
        for kind in _plans_for(spec)[1:]:
            p = compile_plan(spec, kind)
            assert p.shifts <= direct.shifts, (kind, spec.offsets)
            assert p.flops <= direct.flops, (kind, spec.offsets)
