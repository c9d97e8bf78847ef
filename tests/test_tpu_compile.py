"""The engine's kernels compile for a TPU v5e at the sizes users run.

No chip is needed: the TPU compiler compiles for a v5e:2x2 topology that is
described, not attached.  Each test lowers a public entry point with
``interpret=False`` and asserts the compiled program holds the Mosaic
kernel (``tpu_custom_call``) -- the compiler refuses, among others, a kernel
whose VMEM residency exceeds the scoped limit, which interpret mode never
sees.  The topology is described inside a fixture (never at import), and
every test stays in this one file, so under several test workers only the
worker that runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import (compile_plan, stencil_apply, stencil_sharded,
                           stencil_sweep_driver)
from repro.kernels.stencil_engine.autotune import autotune_engine
from repro.sharding.planner import stencil_grid_sharding

KERNEL = 'custom_call_target="tpu_custom_call"'
N = 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-topology compile cannot be read back from a persistent
    # cache without the chip: keep the cache out of these compiles.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernels(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(KERNEL)


def _shapes(sharding, shape, dtype, wshape=(2, 2, 2)):
    return (jax.ShapeDtypeStruct(shape, dtype, sharding=sharding),
            jax.ShapeDtypeStruct(wshape, jnp.float32, sharding=sharding))


@pytest.mark.parametrize("shape,dtype,tiled", [
    ((N, N, N), jnp.float32, True),          # 1 MiB planes: j-tiled stream
    ((128, 128, 128), jnp.float32, False),   # full-plane stream blocks
    ((128, 128, 128), jnp.bfloat16, False),
])
def test_default_stream_path_compiles(one_chip, shape, dtype, tiled):
    path, _, bj = autotune_engine(*shape, jnp.dtype(dtype).itemsize,
                                  plan=compile_plan("stencil27"))
    assert path == "stream" and (bj is not None) == tiled
    n = _kernels(lambda a, w: stencil_apply(a, w, "stencil27",
                                            interpret=False),
                 *_shapes(one_chip, shape, dtype))
    assert n == 1


def test_wavefront_s4_compiles(one_chip):
    n = _kernels(lambda a, w: stencil_sweep_driver(
        a, w, "stencil27", sweeps=4, mode="wavefront", interpret=False),
        *_shapes(one_chip, (N, N, N), jnp.float32))
    assert n == 1


def test_replicate_path_compiles(one_chip):
    n = _kernels(lambda a, w: stencil_apply(a, w, "stencil27",
                                            path="replicate",
                                            interpret=False),
                 *_shapes(one_chip, (N, N, N), jnp.float32))
    assert n == 1


def test_grid_2x2x1_overlap_compiles(topo):
    """The 4-chip (2, 2, 1) grid with overlap on: the interior kernel plus
    the two boundary-strip kernels, and the halo collective-permutes."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("x", "y"),
                axis_types=(AxisType.Auto,) * 2)
    shape, axes = (2 * N, N, N), ("x", "y", None)
    gplan = stencil_grid_sharding(shape, mesh, axes=axes, sweeps=1)
    assert gplan.n_shards == (2, 2, 1)
    a = jax.ShapeDtypeStruct(shape, jnp.float32,
                             sharding=NamedSharding(mesh, P("x", "y", None)))
    w = jax.ShapeDtypeStruct((2, 2, 2), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    txt = jax.jit(lambda a, w: stencil_sharded(
        a, w, "stencil27", mesh=mesh, axes=axes, shard_plan=gplan,
        overlap="on", interpret=False)).lower(a, w).compile().as_text()
    assert txt.count(KERNEL) == 3
    assert "collective-permute" in txt
