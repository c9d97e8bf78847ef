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


# name -> (field shape, bc, block_j) on the (4,) chain: 11 resident
# planes a shard, 13 once extended by the 1-plane halo a side
SLAB_CASES = {"chain-clamp": ((44, 8, 128), "clamp", None),
              "chain-periodic": ((44, 8, 128), "periodic", None),
              "chain-clamp-jtiled": ((44, 32, 128), "clamp", 16)}

SLAB_SCRIPT = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.extend import core
    from repro.kernels import stencil_sharded

    def eqns(jaxpr):
        # every equation of the program, the kernels' own bodies left out
        for e in jaxpr.eqns:
            yield e
            if e.primitive.name == "pallas_call":
                continue
            for v in e.params.values():
                for sub in v if isinstance(v, (list, tuple)) else [v]:
                    if isinstance(sub, core.ClosedJaxpr):
                        sub = sub.jaxpr
                    if isinstance(sub, core.Jaxpr):
                        yield from eqns(sub)

    mesh = jax.make_mesh((4,), ("i",))
    w = np.asarray([[[26, -1], [-1, -1]], [[-1, -1], [-1, -1]]], np.float32)
    out = {{}}
    for name, (shape, bc, bj) in {cases!r}.items():
        u = jnp.ones(shape, jnp.float32)
        def call(u, w):
            return stencil_sharded(u, w, "stencil27", mesh=mesh, axis="i",
                                   bc=bc, block_j=bj)
        jaxpr = jax.make_jaxpr(call)(u, w).jaxpr
        rows = []
        for e in eqns(jaxpr):
            rows.append([e.primitive.name,
                         [list(getattr(v.aval, "shape", ()))
                          for v in e.invars],
                         [list(v.aval.shape) for v in e.outvars]])
        out[name] = rows
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def shard_programs():
    """``{case: [[primitive, in shapes, out shapes]]}``: the equations of
    the sharded call's traced program, the kernels' bodies left out (their
    working strips are ``2h`` planes deeper than their blocks)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(ROOT / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run(
        [sys.executable, "-c", SLAB_SCRIPT.format(cases=SLAB_CASES)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize("case", sorted(SLAB_CASES))
def test_no_i_extended_slab_around_the_kernel(shard_programs, case):
    """The stream kernel takes the exchanged i planes as operands: no
    concatenation or pad makes a slab of ``m_l + 2h`` planes, no slice
    crops such a kernel output, and the kernel writes the local shard."""
    (m, n, p), _, _ = SLAB_CASES[case]
    m_l, h = m // 4, 1
    r = shard_programs[case]
    kernels = [(ins, outs) for name, ins, outs in r if name == "pallas_call"]
    assert len(kernels) == 1, r
    ins, outs = kernels[0]
    assert outs == [[1, m_l, n, p]], kernels
    # the two exchanged slabs ride in as operands
    assert [1, h, n, p] in ins, kernels
    for name, ins, outs in r:
        if name in ("concatenate", "pad"):
            assert all(o[-3:] != [m_l + 2 * h, n, p] for o in outs), outs
        if name in ("slice", "dynamic_slice"):
            assert ins[0][-3:] != [m_l + 2 * h, n, p], ins


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
