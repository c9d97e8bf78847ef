"""The check that decides ``correct`` fails what it must: the lower-precision
control, and each fault a cell can have, planted under the timed path of a
run (CPU, tiny cells, Pallas in interpret mode; only the chip check is
skipped).  The four-chip faults run on four virtual CPU devices in a child
process, since this process must keep seeing one device."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

import control
import repro.kernels as K
import run
from bench_tiny import BENCH

SEED = 2**35 + 17


def _unchanged(u, w, *a, **k):
    return u                              # a step that returns its state


def _half_left_out(real):
    def call(u, w, *a, **k):              # the second half of i unswept
        out = real(u, w, *a, **k)
        half = u.shape[0] // 2
        return out.at[half:].set(u[half:])
    return call


def _answer_altered(real):
    def call(u, w, *a, **k):              # one point off, where produced, by
        out = real(u, w, *a, **k)         # a thousandth of the largest value
        return out.at[3, 5, 7].add(1e-3 * jax.numpy.abs(out).max())
    return call


FAULTS = {"unchanged": lambda real: _unchanged,
          "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}
ENTRY = {"tiny.apply": "stencil_apply", "tiny.smooth4": "stencil_sweep_driver"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", sorted(ENTRY))
def test_fault_under_the_timed_path_is_not_correct(tiny_root, monkeypatch,
                                                   cell, fault):
    real = getattr(K, ENTRY[cell])
    monkeypatch.setattr(K, ENTRY[cell], FAULTS[fault](real))
    res = run.run_cell(tiny_root, cell, SEED, 0.1, False, jax.devices()[:1])
    assert res["correct"] is False
    c = res["checks"]["max_rel_err"]
    assert c["value"] is None or c["value"] > c["limit"]


@pytest.mark.parametrize("cell", sorted(ENTRY))
def test_control_reads_above_the_limit_and_the_program_below(tiny_root,
                                                             cell):
    """The control, the program's own bf16 path, fails the limit; the
    program at its configured f32 passes it, on the same seeds."""
    rows = control.readings(tiny_root, cell, [SEED, SEED + 1, SEED + 2], 0.1,
                            jax.devices()[:1])
    limit = run.find_cell(tiny_root, cell).config["check"]["limit"]
    for r in rows:
        assert r["program_correct"] and r["program"] <= limit
        assert not r["control_correct"] and r["control"] > 3 * limit


FOUR_CHIPS = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {bench!r})
    sys.path.insert(0, {tests!r})
    from pathlib import Path
    import jax, jax.numpy as jnp
    import repro.kernels as K
    import run
    from test_bench_faults import FAULTS

    root, cell, seed = Path({root!r}), "tiny-x4.apply", {seed}
    devs = jax.devices()
    assert len(devs) == 4, devs
    out = {{}}
    if {exchange}:
        # The exchange between chips left out: every halo arrives as zeros.
        # Its own process: the engine keeps the program it traced first.
        jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
        out["exchange_left_out"] = run.run_cell(root, cell, seed, 0.1, False,
                                                devs)
    else:
        out["sound"] = run.run_cell(root, cell, seed, 0.1, True, devs)
        real = K.stencil_sharded
        for name, fault in FAULTS.items():
            K.stencil_sharded = fault(real)
            out[name] = run.run_cell(root, cell, seed, 0.1, False, devs)
    print(json.dumps(out))
""")


@pytest.mark.parametrize("exchange", [False, True])
def test_four_chip_cell_on_virtual_devices(tiny_root, exchange):
    """The sharded cell runs correct on four virtual devices, traced; each
    fault, the exchange left out among them, makes it not correct."""
    script = FOUR_CHIPS.format(bench=str(BENCH), tests=str(BENCH / "tests"),
                               root=str(tiny_root), seed=SEED,
                               exchange=exchange)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    if exchange:
        assert out["exchange_left_out"]["correct"] is False
        return
    sound = out.pop("sound")
    assert sound["correct"] is True and sound["device"]["count"] == 4
    assert list(sound) == ["correct", "attempted", "failed", "metrics",
                           "device", "breakdown", "checks"]
    assert set(out) == set(FAULTS)
    for name, res in out.items():
        assert res["correct"] is False, name
