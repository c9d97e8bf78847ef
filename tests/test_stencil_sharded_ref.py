"""``stencil_sharded`` on a (4,) mesh against the benchmark's plain reference.

The ``j27-f32-x4`` deployment (``bench/configs/``) runs ``stencil_sharded``
with the engine's own plan, path and blocks on a field sharded on i, and
the benchmark's ``correct`` compares its output with
``bench/references/stencil27.py``, plain ``jnp`` written apart from the
engine.  These tests make the same comparison on four virtual CPU devices
(Pallas interpreted), on seeded uniform f32 data, under ``clamp`` and
``periodic``, at shapes that take the two geometries the deployment takes
on the chip: a halo-extended slab the engine blocks with an odd
``block_i`` (its 1026 planes a chip get 3), and a j-tiled stream call
whose j-halo narrowing engages.  The geometry is read from the program's
own ``stencil.select`` and ``stencil.trace`` spans.  Every case runs in one child process, since this
one must keep seeing one device.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

# f32 rounding: the engine and the reference add the same 27 products in
# different orders (the engine's plan factors shared partial sums), so each
# output may differ by a few ulps of the largest term, 26 * |u| <= 26 in a
# field whose largest output is about 40: a few 1e-7 over the largest
# reference magnitude.  A bf16 field rounds each input by up to 2**-9 and
# reads about 4e-3, so it cannot pass.
TOL = 1e-6

# name -> (global shape, block_j passed, whether halo_rows must engage)
CASES = {"odd_bi": ((28, 16, 128), None, False),
         "jtiled": ((36, 32, 128), 16, True)}
BCS = ("clamp", "periodic")

SCRIPT = textwrap.dedent("""
    import json, os, sys
    import jax, jax.numpy as jnp, numpy as np
    sys.path.insert(0, {refs!r})
    sys.path.insert(0, {tests!r})
    import stencil27
    from test_stencil_spans import record
    from repro.kernels import stencil_sharded
    from repro.kernels.stencil_engine import common

    assert len(jax.devices()) == 4, jax.devices()
    mesh = jax.make_mesh((4,), ("i",))
    w = [[[26, -1], [-1, -1]], [[-1, -1], [-1, -1]]]
    wa = np.asarray(w, np.float32)
    out = {{}}
    for name, (shape, bj, _) in {cases!r}.items():
        seed = sum(map(ord, name))
        key = jax.random.key(seed)
        u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
        # the first call at this shape traces the shard body: its spans
        # carry what the program chose for the halo-extended slab
        trace_dir = os.path.join({trace!r}, name)
        spans = record(trace_dir, lambda: stencil_sharded(
            u, wa, "stencil27", mesh=mesh, axis="i", block_j=bj, bc="clamp"))
        (sel,) = [s[3] for s in spans if s[0] == common.SPAN_SELECT]
        (body,) = [s[3] for s in spans if s[0] == common.SPAN_TRACE]
        for bc in {bcs!r}:
            want = stencil27.reference(u, w, bc, 1)
            scale = float(jnp.max(jnp.abs(want)))
            errs = {{}}
            for dt in ("float32", "bfloat16"):
                got = stencil_sharded(u.astype(dt), wa, "stencil27",
                                      mesh=mesh, axis="i", block_j=bj, bc=bc)
                gap = jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                errs[dt] = float(gap) / scale
            out[f"{{name}}-{{bc}}"] = dict(select=sel, trace=body, **errs)
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    """``{"<case>-<bc>": {select, trace, float32, bfloat16}}``: the
    arguments of the clamp call's ``stencil.select`` and shard-body
    ``stencil.trace`` spans (the program's choice for the halo-extended
    slab), and the widest gap over the reference's largest magnitude of
    each field dtype."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(ROOT / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    script = SCRIPT.format(refs=str(ROOT / "bench" / "references"),
                           tests=str(ROOT / "tests"), cases=CASES, bcs=BCS,
                           trace=str(tmp_path_factory.mktemp("spans")))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize("bc", BCS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_sharded_matches_plain_reference(readings, case, bc):
    r = readings[f"{case}-{bc}"]
    assert r["select"]["path"] == "stream"
    assert r["float32"] <= TOL, r


@pytest.mark.parametrize("case", sorted(CASES))
def test_sharded_geometry_is_the_cells(readings, case):
    """The odd block on the extended slab, and the j-halo narrowing, are
    what the program ran, as its spans record it: the cases test the
    geometry they name."""
    shape, bj, narrowed = CASES[case]
    r = readings[f"{case}-clamp"]
    sel, body = r["select"], r["trace"]
    assert sel["halo"] == str((1, 0, 0)), r
    assert sel["local"] == str((shape[0] // 4, *shape[1:])), r
    assert sel["block_i"] % 2 == 1 and body["block_i"] == sel["block_i"], r
    if narrowed:
        assert sel["block_j"] == body["block_j"] == bj, r
        assert body["halo_rows"] == 8, r
    else:
        assert "block_j" not in body and "halo_rows" not in body, r


@pytest.mark.parametrize("bc", BCS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_bf16_field_fails_the_tolerance(readings, case, bc):
    assert readings[f"{case}-{bc}"]["bfloat16"] > 100 * TOL
