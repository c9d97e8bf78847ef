"""``stencil_sharded`` runs nothing eagerly on the field.

The jitted shard_map program takes the caller's ``(..., M, N, P)`` field as
it is and does both of its reshapes inside, where they lower to bitcasts.
Run eagerly, each reshape is a program of its own, and its executable
copies the whole shard (an executable cannot return a view of an input it
was not given to keep): one copy before the call and one after.  These
tests read JAX's compile log during a cold call, on four virtual CPU
devices in a child process, and assert that no standalone ``reshape``
program took the field's points, on the i-chain and on a (2, 2, 1) process
grid.  The weights' own reshape (8 numbers) is the host's choice and is
left out by its size.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

# name -> (field shape, mesh shape, axes); shapes no other test runs, so
# every program compiles cold
CASES = {"chain": ((44, 8, 128), (4,), ("i",)),
         "chain_batched": ((2, 1, 44, 8, 128), (4,), ("i",)),
         "grid": ((20, 12, 128), (2, 2, 1), ("x", "y", "z"))}

SCRIPT = textwrap.dedent("""
    import json, logging, math
    import jax, jax.numpy as jnp, numpy as np
    from repro.kernels import stencil_sharded

    assert len(jax.devices()) == 4, jax.devices()
    compiled = []

    class Log(logging.Handler):
        def emit(self, rec):
            if str(rec.msg).startswith("Compiling %s with global shapes"):
                name, avals = rec.args[0], rec.args[1]
                compiled.append([name, [math.prod(v.shape) for v in avals]])

    logging.getLogger("jax").addHandler(Log())
    jax.config.update("jax_log_compiles", True)
    w = np.asarray([[[26, -1], [-1, -1]], [[-1, -1], [-1, -1]]], np.float32)
    out = {{}}
    for name, (shape, mesh_shape, names) in {cases!r}.items():
        mesh = jax.make_mesh(mesh_shape, names)
        u = jnp.ones(shape, jnp.float32)
        kw = (dict(axis=names[0]) if len(names) == 1
              else dict(axes=names))
        compiled.clear()
        got = stencil_sharded(u, w, "stencil27", mesh=mesh, bc="clamp", **kw)
        out[name] = dict(compiled=list(compiled), shape=list(got.shape))
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def compiles():
    """``{case: {compiled: [[program, [points of each argument]]],
    shape: output shape}}`` of one cold call per case."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(ROOT / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(cases=CASES)], env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize("case", sorted(CASES))
def test_no_eager_reshape_of_the_field(compiles, case):
    shape = CASES[case][0]
    field = math.prod(shape)
    r = compiles[case]
    assert r["shape"] == list(shape), r
    takes_field = [name for name, sizes in r["compiled"] if field in sizes]
    # the program that runs the shard body compiled, and took the field
    assert takes_field, r
    assert not any("reshape" in name for name in takes_field), r
