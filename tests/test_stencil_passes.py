"""Pass-pipeline unit tests: each plan-compiler pass is checked on its own
op-count / liveness / dataflow invariants, and the presets reproduce the
contracted static counts (incl. the radius-2 acceptance numbers)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _x64 import x64

from repro.kernels import compile_plan, get_stencil, spec_from_mask
from repro.kernels.stencil_engine.plan import (PASS_PRESETS, build_direct,
                                               cse, mirror_factor,
                                               mirror_symmetric, order_ops,
                                               peak_live, run_passes)
from repro.kernels.stencil_engine.plan.ir import op_sources

BUILTINS = ("stencil3", "stencil7", "stencil27", "star13", "box125")


def test_presets_and_pass_recording():
    """The former monolithic plan kinds are pass-list presets, and each
    compiled plan records the pipeline that produced it."""
    assert PASS_PRESETS["direct"] == ("build_direct",)
    assert PASS_PRESETS["cse"][0] == "build_direct" and \
        "cse" in PASS_PRESETS["cse"]
    assert "mirror_factor" in PASS_PRESETS["factored"]
    d = compile_plan("stencil27", "direct")
    assert d.passes == ("build_direct",)
    f = compile_plan("stencil27", "factored")
    assert f.passes[0] == "build_direct" and "mirror_factor" in f.passes
    assert f.passes[-1].startswith("order_ops")


def test_run_passes_error_paths():
    spec = get_stencil("stencil7")
    with pytest.raises(ValueError, match="build_direct"):
        run_passes(spec, ("cse",))
    with pytest.raises(ValueError, match="unknown pass"):
        run_passes(spec, ("build_direct", "vectorize"))


def test_build_direct_counts():
    """One shift per nonzero offset component per tap (a radius-2 component
    is one magnitude-2 shift), one multiply-add per tap."""
    for name, shifts, flops in (("stencil27", 54, 53), ("star13", 12, 25),
                                ("box125", 300, 249)):
        p = build_direct(get_stencil(name))
        assert (p.shifts, p.flops) == (shifts, flops), name
        # direct peak liveness is constant: u, the tap chain, the accumulator
        assert peak_live(p) <= 4, name


def test_cse_pass_invariants():
    """cse never emits more shifts than direct and never changes flops."""
    for name in BUILTINS:
        spec = get_stencil(name)
        d = build_direct(spec)
        c = cse(d)
        assert c.kind == "cse" and c.passes[-1] == "cse"
        assert c.shifts <= d.shifts and c.flops == d.flops, name
    assert cse(build_direct(get_stencil("stencil27"))).shifts == 10
    assert cse(build_direct(get_stencil("box125"))).shifts == 28


def test_mirror_factor_radius2_acceptance():
    """Acceptance: the factored radius-2 star plan statically beats its
    direct schedule on shifts+flops (like the stencil27 8+19 check), and
    box125 collapses from 300 shifts to 20."""
    d13 = compile_plan("star13", "direct")
    f13 = compile_plan("star13", "factored")
    assert (d13.shifts, d13.flops) == (12, 25)
    assert (f13.shifts, f13.flops) == (12, 19)
    assert f13.shifts + f13.flops < d13.shifts + d13.flops
    assert f13.shifts <= d13.shifts and f13.flops < d13.flops

    d125 = compile_plan("box125", "direct")
    f125 = compile_plan("box125", "factored")
    assert (f125.shifts, f125.flops) == (20, 63)
    assert f125.shifts * 3 <= d125.shifts
    assert f125.flops <= 0.4 * d125.flops

    # the stencil27 contract is unchanged by the pass restructuring
    f27 = compile_plan("stencil27", "factored")
    assert (f27.shifts, f27.flops) == (8, 19)


def test_mirror_factor_noop_on_asymmetric():
    mask = np.zeros((3, 3, 3), bool)
    mask[1, 1, 1] = mask[1, 1, 2] = True
    spec = spec_from_mask("asym-noop", mask)
    assert not mirror_symmetric(spec)
    d = build_direct(spec)
    assert mirror_factor(d) is d


def test_order_ops_never_increases_liveness_on_builtins():
    """Acceptance: the order_ops pass provably never increases peak SSA
    liveness on the builtin specs, for every preset pipeline stage it can
    follow -- and its reordering preserves the op multiset and the SSA
    topological property."""
    for name in BUILTINS:
        spec = get_stencil(name)
        pres = [build_direct(spec), cse(build_direct(spec))]
        if mirror_symmetric(spec):
            pres.append(mirror_factor(build_direct(spec)))
        for pre in pres:
            post = order_ops(pre)
            assert peak_live(post) <= peak_live(pre), (name, pre.kind)
            assert post.passes[-1].startswith("order_ops")
            # op multiset (kind, off, w_idx) unchanged -- pure reordering
            key = lambda p: sorted((o.kind, o.off, o.w_idx) for o in p.ops)
            assert key(post) == key(pre), (name, pre.kind)
            assert (post.shifts, post.flops) == (pre.shifts, pre.flops)
            # valid SSA numbering: every op only reads earlier values
            for i, op in enumerate(post.ops):
                assert all(v <= i for v in op_sources(op)), (name, i)
            assert 0 <= post.out <= len(post.ops)


def test_order_ops_actually_reduces_pressure_somewhere():
    """Not just 'never worse': on the wide radius-2 box the grouped cse
    schedule's working set shrinks materially under the scheduler order."""
    spec = get_stencil("box125")
    pre = cse(build_direct(spec))
    post = order_ops(pre)
    assert peak_live(post) < peak_live(pre)


def test_peak_live_hand_example():
    """peak_live on a hand-built plan: u shifted twice, summed -- both
    shifts are live together, then the sum replaces them."""
    from repro.kernels.stencil_engine.plan.ir import Builder, StencilPlan
    spec = get_stencil("stencil3")
    b = Builder()
    l = b.shift(0, 2, -1)
    r = b.shift(0, 2, 1)
    s = b.add(l, r)
    plan = StencilPlan(spec=spec, kind="direct", ops=tuple(b.ops), out=s)
    # u + l -> u + l + r (peak: u, l, r) -> s (u dead after r, l/r die at s)
    assert peak_live(plan) == 3


def test_ordered_plans_execute_identically():
    """order_ops is pure reordering: on integer-valued f64 data every
    pipeline stage (before/after ordering) produces bit-identical results
    through the executor."""
    from repro.kernels import stencil_ref
    rng = np.random.default_rng(3)
    with x64():
        a = jnp.asarray(rng.integers(-4, 5, (8, 10, 16)), jnp.float64)
        for name in ("stencil27", "star13", "box125"):
            spec = get_stencil(name)
            w = jnp.asarray(rng.integers(1, 4, spec.w_shape), jnp.float64)
            base = np.asarray(stencil_ref(a, w, name, plan="direct"))
            for kind in ("cse", "factored"):
                got = np.asarray(stencil_ref(a, w, name, plan=kind))
                np.testing.assert_array_equal(got, base)


def test_describe_reports_radius_and_pass_list():
    d = compile_plan("star13", "factored").describe()
    assert d["radius"] == [2, 2, 2]
    assert d["pass_list"][0] == "build_direct"
    assert "peak_live" in d and d["peak_live"] >= 1
    assert d["taps"] == 13
