"""Guarded execution: every fault injector caught by its matching detector,
bit-exact ladder recovery vs the oracle, guard="off" byte-identity with the
historical programs, the 2-device corrupted halo exchange (subprocess), the
report schema, and the satellite harness fixes (regression-gate exit codes,
benchmark wall-clock timeout)."""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _x64 import x64

from repro.kernels import (GuardPolicy, guard_bytes_per_point,
                           last_guard_report, stencil_apply, stencil_ref,
                           stencil_sharded, stencil_sweep_driver)
from repro.kernels.stencil_engine import (GUARD_KINDS, LADDER, BitFlipPlane,
                                          CorruptHalo, GuardError,
                                          NaNScratchWindow, NaNWindow,
                                          RaisingCandidate, as_guard,
                                          clear_blacklist, get_stencil,
                                          inject, is_blacklisted,
                                          list_blacklist, run_guard_checks,
                                          stencil_ref_planes)
from repro.kernels.stencil_engine import guard as guard_mod
from repro.kernels.stencil_engine.ops import stencil_apply_jit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(23)


def _int_field(shape):
    """Integer-valued f64 data: every path/rung is exact, so recovery can be
    asserted with ``assert_array_equal`` (bit-exact vs the oracle)."""
    return jnp.asarray(RNG.integers(-4, 5, shape).astype(np.float64))


def _int_weights(n):
    return jnp.asarray(RNG.integers(-3, 4, n).astype(np.float64))


N_WEIGHTS = {"stencil7": 4, "stencil27": 8, "star13": 3}


def _nw(name):
    return N_WEIGHTS[name.split("_")[0]]


@pytest.fixture(autouse=True)
def _clean_blacklist():
    clear_blacklist()
    yield
    clear_blacklist()


# ---------------------------------------------------------------------------
# Policy spellings and the off-path bypass.
# ---------------------------------------------------------------------------

def test_as_guard_spellings():
    assert as_guard(None) is None and as_guard("off") is None
    assert as_guard("nan") == GuardPolicy(nan=True, invariant=False,
                                          oracle=False, sample=0)
    assert as_guard("invariant").invariant and not as_guard("invariant").oracle
    assert as_guard("oracle").oracle and as_guard("oracle").sample == 4
    full = as_guard("full")
    assert full.oracle and full.sample == 0
    pol = GuardPolicy(sample=2, retries=0)
    assert as_guard(pol) is pol
    with pytest.raises(ValueError, match="unknown guard"):
        as_guard("bogus")
    with pytest.raises(ValueError):
        GuardPolicy(sample=-1)
    with pytest.raises(ValueError):
        GuardPolicy(retries=-1)


def test_spec_guard_field_validated():
    spec = get_stencil("stencil7")
    assert spec.guard == "off"
    for kind in GUARD_KINDS:
        assert spec.with_guard(kind).guard == kind
    with pytest.raises(ValueError, match="unknown guard"):
        spec.with_guard("bogus")


def test_guard_off_is_byte_identical_and_never_checks():
    """The default dispatches straight to the historical jitted program:
    same bytes out as calling it directly, and the guard's check machinery
    never runs."""
    with x64():
        a = _int_field((12, 8, 32))
        w = _int_weights(4)
        before = guard_mod.CHECK_RUNS[0]
        off = stencil_apply(a, w, "stencil7")               # spec default
        off2 = stencil_apply(a, w, "stencil7", guard="off")  # explicit
        jit_direct = stencil_apply_jit(a, w, "stencil7")
        assert guard_mod.CHECK_RUNS[0] == before
        np.testing.assert_array_equal(np.asarray(off), np.asarray(jit_direct))
        np.testing.assert_array_equal(np.asarray(off2),
                                      np.asarray(jit_direct))
        # no injectors installed -> the hook lists really are empty
        assert not guard_mod._OUT_HOOKS and not guard_mod._RUN_HOOKS
        drv = stencil_sweep_driver(a, w, "stencil7", sweeps=2)
        assert guard_mod.CHECK_RUNS[0] == before
        assert drv.shape == a.shape


@pytest.mark.parametrize("name", ["stencil7", "stencil7_periodic",
                                  "stencil7_neumann", "stencil27_redblack"])
def test_guarded_clean_run_matches_off(name):
    """A clean guarded call is byte-identical to the unguarded program (the
    guard only *observes*), passes its checks, and reports final == start
    with no demotions -- across BC x ordering (no false positives)."""
    with x64():
        a = _int_field((12, 8, 32))
        w = _int_weights(_nw(name))
        off = stencil_apply(a, w, name)
        got = stencil_apply(a, w, name, guard="oracle")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(off))
        rep = last_guard_report()
        assert rep.final == rep.start == "fused"
        assert rep.demotions == [] and rep.blacklisted == []
        assert all(c["ok"] for c in rep.attempts[0]["checks"])


@pytest.mark.parametrize("name", ["stencil7", "stencil7_periodic",
                                  "stencil27_neumann"])
@pytest.mark.parametrize("s", [1, 2])
def test_run_guard_checks_no_false_positives(name, s):
    """The detectors stay silent on honest outputs, sampled and full, and
    the sampled strip oracle agrees with the full reference."""
    with x64():
        a = _int_field((14, 8, 32))
        w = _int_weights(_nw(name))
        spec = get_stencil(name)
        out = stencil_sweep_driver(a, w, name, sweeps=s)
        for policy in (GuardPolicy(oracle=True, sample=4),
                       GuardPolicy(oracle=True, sample=0)):
            recs = run_guard_checks(out, a, w, spec, s, policy)
            assert all(c["ok"] for c in recs), recs
        h = spec.radius[0] * spec.sweep_apps * s
        if spec.bc[0][0].kind == "periodic":
            planes = np.asarray([0, h + 1, a.shape[0] - 1])
        else:                        # strip oracle wants interior planes
            planes = np.asarray([h, h + 1, a.shape[0] - 1 - h])
        strips = stencil_ref_planes(a, w, spec, planes, sweeps=s)
        full = stencil_ref(a, w, spec, sweeps=s)
        np.testing.assert_array_equal(np.asarray(strips),
                                      np.asarray(full)[planes])


# ---------------------------------------------------------------------------
# Each injector vs its matching detector (+ bit-exact recovery).
# ---------------------------------------------------------------------------

def test_nan_window_caught_by_nan_screen_retry_recovers():
    """A one-shot NaN store: the nan check fails attempt 0, the same-rung
    retry runs clean -- no demotion, bit-exact result."""
    with x64():
        a = _int_field((12, 8, 32))
        w = _int_weights(4)
        with inject(NaNWindow(seed=7, plane=5)) as (inj,):
            out = stencil_apply(a, w, "stencil7", guard="full")
        assert inj.fired == 1
        rep = last_guard_report()
        assert rep.attempts[0]["fault"] == "nan"
        assert not [c for c in rep.attempts[0]["checks"]
                    if c["check"] == "nan"][0]["ok"]
        assert rep.final == "fused" and rep.demotions == []
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(stencil_ref(a, w, "stencil7")))


def test_bitflip_plane_caught_by_invariant():
    """An exponent-bit flip is huge but *finite*: it sails through the NaN
    screen and the weight-sum invariant trips."""
    with x64():
        a = _int_field((12, 8, 32))
        w = _int_weights(4)
        with inject(BitFlipPlane(seed=3, plane=6)) as (inj,):
            out = stencil_apply(a, w, "stencil7_periodic", guard="full")
        assert inj.fired == 1
        rep = last_guard_report()
        checks = {c["check"]: c for c in rep.attempts[0]["checks"]}
        assert checks["nan"]["ok"]          # finite -- the screen passes
        assert not checks["invariant"]["ok"]
        np.testing.assert_array_equal(
            np.asarray(out),
            np.asarray(stencil_ref(a, w, "stencil7_periodic")))


def test_nan_scratch_kernel_fault_demotes_off_stream():
    """A NaN poisoned inside the stream kernel's VMEM rotating window (the
    static ``_fault`` hook): the screen catches it on the fused rung, the
    retry re-fires, and the ladder recovers on a lower rung, bit-exact."""
    with x64():
        a = _int_field((12, 8, 32))
        w = _int_weights(4)
        with inject(NaNScratchWindow(seed=1, plane=2, fires=3)) as (inj,):
            out = stencil_apply(a, w, "stencil7", guard="full",
                                path="stream")
        assert inj.fired == 3
        rep = last_guard_report()
        assert rep.demotions and rep.demotions[0]["from"] == "fused"
        assert rep.demotions[0]["fault"] == "nan"
        assert rep.final != "fused"
        assert rep.blacklisted == []     # data fault, not a raising kernel
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(stencil_ref(a, w, "stencil7")))


def test_raising_candidate_demotes_and_blacklists():
    """A candidate that raises at run time: retried once, demoted, and the
    dead rung blacklisted in the autotuner so future auto races skip it."""
    with x64():
        a = _int_field((12, 8, 32))
        w = _int_weights(4)
        with inject(RaisingCandidate(rungs=("fused", "chained"))) as (inj,):
            out = stencil_apply(a, w, "stencil7", guard="full")
        assert inj.fired == 4            # 2 rungs x (attempt + retry)
        rep = last_guard_report()
        assert [d["fault"] for d in rep.demotions] == \
            ["exception:RuntimeError"] * 2
        assert [d["retries"] for d in rep.demotions] == [1, 1]
        assert rep.final == "stream"
        assert ("mode", "fused") in rep.blacklisted
        assert ("mode", "chained") in rep.blacklisted
        assert is_blacklisted("stencil7", mode="fused")
        assert is_blacklisted("stencil7", mode="chained")
        assert ("stencil7", "mode", "fused") in list_blacklist()
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(stencil_ref(a, w, "stencil7")))


def test_ladder_exhaustion_raises_guard_error():
    """When every rung (the oracle included) dies, the guard refuses to
    return unverified data."""
    with x64():
        a = _int_field((8, 8, 32))
        w = _int_weights(4)
        with inject(RaisingCandidate(rungs=LADDER)):
            with pytest.raises(GuardError, match="every ladder rung"):
                stencil_apply(a, w, "stencil7", guard="full")


def test_corrupt_halo_unsharded_caught():
    """The single-device analogue of a bad exchange: corrupted edge planes
    trip the invariant, and the retry recovers bit-exactly."""
    with x64():
        a = _int_field((12, 8, 32))
        w = _int_weights(4)
        with inject(CorruptHalo(seed=9, mode="garbage",
                                sharded=False)) as (inj,):
            out = stencil_apply(a, w, "stencil7_periodic", guard="full")
        assert inj.fired == 1
        rep = last_guard_report()
        assert rep.attempts[0]["fault"] == "invariant"
        np.testing.assert_array_equal(
            np.asarray(out),
            np.asarray(stencil_ref(a, w, "stencil7_periodic")))


# ---------------------------------------------------------------------------
# Guarded driver / sharded entries.
# ---------------------------------------------------------------------------

def test_guarded_driver_clean_wavefront():
    with x64():
        a = _int_field((16, 8, 32))
        w = _int_weights(4)
        off = stencil_sweep_driver(a, w, "stencil7", sweeps=3,
                                   mode="wavefront")
        got = stencil_sweep_driver(a, w, "stencil7", sweeps=3,
                                   mode="wavefront", guard="oracle")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(off))
        rep = last_guard_report()
        assert rep.entry == "driver" and rep.sweeps == 3
        assert rep.start == rep.final == "wavefront"


def test_guarded_driver_wavefront_demotes_to_fused():
    """A persistent fault on the wavefront rung (fires through the retry)
    walks the driver down to the fused rung, bit-exact."""
    with x64():
        a = _int_field((16, 8, 32))
        w = _int_weights(4)
        with inject(NaNWindow(seed=2, plane=7, rungs=("wavefront",),
                              fires=2)) as (inj,):
            out = stencil_sweep_driver(a, w, "stencil7", sweeps=3,
                                       mode="wavefront", guard="full")
        assert inj.fired == 2
        rep = last_guard_report()
        assert rep.demotions == [{"from": "wavefront", "to": "fused",
                                  "fault": "nan", "retries": 1}]
        assert rep.final == "fused"
        np.testing.assert_array_equal(
            np.asarray(out),
            np.asarray(stencil_ref(a, w, "stencil7", sweeps=3)))


def test_guarded_sharded_single_device():
    """The sharded entry point's guard path (1-device mesh): clean run,
    sharded-entry report, bit-exact vs the oracle."""
    with x64():
        a = _int_field((16, 8, 32))
        w = _int_weights(4)
        mesh = jax.make_mesh((1,), ("data",))
        got = stencil_sharded(a, w, "stencil7", mesh=mesh, sweeps=2,
                              guard="oracle")
        rep = last_guard_report()
        assert rep.entry == "sharded" and rep.final == "fused"
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(stencil_ref(a, w, "stencil7", sweeps=2)))


def test_sharded_corrupt_halo_2dev_subprocess():
    """2 forced host devices: corrupt the ppermute'd halo slabs inside the
    traced exchange (garbage / truncate / nan), and show each detector
    firing and the ladder escaping the sharded path to recover bit-exactly
    on a single-device rung."""
    code = """
    import jax, numpy as np, jax.numpy as jnp
    from repro.kernels import stencil_ref, stencil_sharded, last_guard_report
    from repro.kernels.stencil_engine import CorruptHalo, inject
    assert jax.device_count() == 2
    with jax.enable_x64(True):
        rng = np.random.default_rng(5)
        a = jnp.asarray(rng.integers(-4, 5, (16, 8, 32)).astype(np.float64))
        w = jnp.asarray(rng.integers(-3, 4, 4).astype(np.float64))
        mesh = jax.make_mesh((2,), ("data",))
        ref = stencil_ref(a, w, "stencil7_periodic", sweeps=2)
        for mode, detector in (("garbage", "invariant"),
                               ("truncate", "invariant"), ("nan", "nan")):
            with inject(CorruptHalo(mode=mode)) as (inj,):
                got = stencil_sharded(a, w, "stencil7_periodic", mesh=mesh,
                                      sweeps=2, guard="full")
            assert inj.fired >= 1
            rep = last_guard_report()
            assert rep.entry == "sharded"
            assert rep.attempts[0]["fault"] == detector, (mode, rep.attempts)
            assert rep.demotions, mode
            assert rep.final in ("chained", "stream", "replicate"), mode
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        print("halo faults ok")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "halo faults ok" in out.stdout


# ---------------------------------------------------------------------------
# Report schema and the overhead model.
# ---------------------------------------------------------------------------

def test_guard_report_describe_schema():
    with x64():
        a = _int_field((12, 8, 32))
        w = _int_weights(4)
        stencil_apply(a, w, "stencil7", guard="invariant")
        doc = last_guard_report().describe()
    g = doc["guard"]
    assert set(g) == {"spec", "sweeps", "entry", "start", "final", "policy",
                      "attempts", "demotions", "blacklisted"}
    assert g["spec"] == "stencil7" and g["entry"] == "apply"
    assert g["policy"] == {"nan": True, "invariant": True, "oracle": False,
                           "sample": 4, "retries": 1, "rtol": None}
    att = g["attempts"][0]
    assert set(att) == {"rung", "attempt", "checks", "fault"}
    for c in att["checks"]:
        assert set(c) == {"check", "ok", "skipped", "detail"}
    json.dumps(doc)                     # machine-readable end to end


def test_guard_overhead_model_under_gate():
    """The modeled check traffic of the default policy: < 10% of the stream
    path's 2 * itemsize at the benchmark's gate shape, 0 when off."""
    assert guard_bytes_per_point(None, 4, 128) == 0.0
    bpp = guard_bytes_per_point(GuardPolicy(), 4, 128)
    assert bpp == pytest.approx(0.5)
    assert bpp / (2.0 * 4) < 0.10
    # full checks price the whole volume -- debug grade, not gated
    assert guard_bytes_per_point(GuardPolicy(sample=0), 4, 128) == \
        pytest.approx(8.0)
    # sampling never prices more planes than exist
    assert guard_bytes_per_point(GuardPolicy(sample=99), 4, 8) <= \
        guard_bytes_per_point(GuardPolicy(sample=0), 4, 8)


# ---------------------------------------------------------------------------
# Satellites: regression-gate exit codes + benchmark wall-clock timeout.
# ---------------------------------------------------------------------------

def _load_module(rel, name):
    path = os.path.join(REPO, *rel)
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def test_check_regression_bad_baseline_exits_2(tmp_path, capsys):
    """Satellite: a missing / truncated / non-object baseline is a harness
    error (exit 2) with a one-line diagnostic naming the bad file -- never
    a silent pass or a fake regression verdict."""
    cr = _load_module(("benchmarks", "check_regression.py"),
                      "check_regression_guard_test")
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps({"schema": "bench_stencil/v6",
                                 "paths": {"stream":
                                           {"bytes_per_point_f32": 8.0}}}))
    missing = str(tmp_path / "nope.json")
    assert cr.main([missing, str(fresh)]) == 2
    assert "nope.json" in capsys.readouterr().out
    truncated = tmp_path / "trunc.json"
    truncated.write_text('{"schema": "bench_stencil/v6", "paths": {')
    assert cr.main([str(truncated), str(fresh)]) == 2
    msg = capsys.readouterr().out
    assert "trunc.json" in msg and "JSON" in msg
    listdoc = tmp_path / "list.json"
    listdoc.write_text("[1, 2, 3]")
    assert cr.main([str(listdoc), str(fresh)]) == 2
    assert "expected an object" in capsys.readouterr().out
    # a bad *fresh* file is caught the same way
    assert cr.main([str(fresh), missing]) == 2
    assert "nope.json" in capsys.readouterr().out


def test_bench_runner_timeout(capsys):
    """Satellite: a wedged sub-benchmark is interrupted by the wall-clock
    alarm (BenchTimeout), not left to stall the harness."""
    run = _load_module(("benchmarks", "run.py"), "bench_run_guard_test")
    if not hasattr(__import__("signal"), "SIGALRM"):
        pytest.skip("no SIGALRM on this platform")

    def _hung_rows():
        time.sleep(10)
        yield "never,0,unreached"

    hung = types.SimpleNamespace(run=_hung_rows)
    t0 = time.monotonic()
    with pytest.raises(run.BenchTimeout, match="BENCH_TIMEOUT_S=1"):
        run._run_rows("hung", hung, timeout_s=1)
    assert time.monotonic() - t0 < 5.0
    # a fast benchmark under the same alarm passes untouched
    quick = types.SimpleNamespace(run=lambda: iter(["quick,1.0,ok"]))
    run._run_rows("quick", quick, timeout_s=30)
    assert "quick,1.0,ok" in capsys.readouterr().out


def test_bench_timeout_env_parsing(monkeypatch):
    run = _load_module(("benchmarks", "run.py"), "bench_run_env_test")
    monkeypatch.setenv("BENCH_TIMEOUT_S", "17")
    assert run._timeout_s() == 17
    monkeypatch.setenv("BENCH_TIMEOUT_S", "not-a-number")
    assert run._timeout_s() == run.DEFAULT_TIMEOUT_S
    monkeypatch.setenv("BENCH_TIMEOUT_S", "-3")
    assert run._timeout_s() == 0    # negative disables, never crashes
    monkeypatch.delenv("BENCH_TIMEOUT_S")
    assert run._timeout_s() == run.DEFAULT_TIMEOUT_S


def test_bench_children_pinned_to_cpu_and_failures_raise(monkeypatch):
    """A forced-host-device child runs on the CPU whatever platform the
    parent asks for (the parent may hold the chip), and a child that fails
    raises -- the benchmark run exits non-zero -- instead of leaving a
    FAILED row behind an exit code of 0."""
    st = _load_module(("benchmarks", "stencil_throughput.py"),
                      "bench_children_test")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    rows = st._subprocess_rows(
        "import jax; print(f'probe,1.0,{jax.devices()[0].platform} "
        "n={jax.device_count()} ok=True')", "probe", n_dev=2)
    assert rows == ["probe,1.0,cpu n=2 ok=True"]
    with pytest.raises(RuntimeError, match="exited 3"):
        st._subprocess_rows("import sys; sys.exit(3)", "probe", n_dev=2)
    with pytest.raises(RuntimeError, match="unparseable"):
        st._subprocess_rows("print('not a row')", "probe", n_dev=2)
