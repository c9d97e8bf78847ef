"""The engine's kernels compile for a TPU v5e at the sizes users run.

No chip is needed: the TPU compiler compiles for a v5e:2x2 topology that is
described, not attached.  Each test lowers a public entry point with
``interpret=False`` and asserts the compiled program holds the Mosaic
kernel (``tpu_custom_call``) -- the compiler refuses, among others, a kernel
whose VMEM residency exceeds the scoped limit, which interpret mode never
sees -- and that each kernel carries its path's fixed name, which the
device trace shows.  The topology is described inside a fixture (never at
import), and every test stays in this one file, so under several test
workers only the worker that runs this file loads the TPU library.
"""

import os
import re

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
# A kernel's HLO instruction, named by its pallas_call's name=.
KERNEL_NAME = re.compile(r"%(stencil_[a-z_]+)\.\d+ = ")
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
    assert set(KERNEL_NAME.findall(txt)) == {"stencil_stream",
                                             "stencil_strip"}


def _apply(stencil="stencil27", **kw):
    return lambda a, w: stencil_apply(a, w, stencil, interpret=False, **kw)


@pytest.mark.parametrize("kernel,call,shape,wshape", [
    # the default 512^3 f32 program: 1 MiB planes, j-tiled
    ("stencil_stream_jtiled", _apply(), (N, N, N), (2, 2, 2)),
    ("stencil_stream", _apply(), (128, 128, 128), (2, 2, 2)),
    ("stencil_replicate_jtiled", _apply(path="replicate"), (N, N, N),
     (2, 2, 2)),
    ("stencil_replicate", _apply(path="replicate"), (128, 128, 128),
     (2, 2, 2)),
    ("stencil_wavefront", lambda a, w: stencil_sweep_driver(
        a, w, "stencil27", sweeps=4, mode="wavefront", interpret=False),
     (N, N, N), (2, 2, 2)),
    ("stencil_rows", _apply(stencil="stencil3"), (1024, N), (2,)),
], ids=lambda v: v if isinstance(v, str) else None)
def test_each_kernel_carries_its_name(one_chip, kernel, call, shape,
                                      wshape):
    """One fixed name per pallas_call site (the overlap strip's is checked
    with the grid above); a program holds only its path's kernel."""
    txt = jax.jit(call).lower(
        *_shapes(one_chip, shape, jnp.float32, wshape)).compile().as_text()
    assert set(KERNEL_NAME.findall(txt)) == {kernel}


def test_four_chip_chain_at_the_cells_size_compiles(topo):
    """The ``j27-f32-x4`` deployment: 4096x512x512 f32 on a (4,) mesh on
    i, each chip's 1024 planes extended by one exchanged plane a side to
    1026 and swept by the j-tiled stream kernel.  Two halo
    collective-permutes; the slab, the kernel's output and the crop fit a
    chip with the field and its output.  The program ``stencil_sharded``
    launches takes the caller's (M, N, P) field as it is and reshapes it
    to (1, M, N, P) and back inside, so both reshapes lower to bitcasts:
    no copy of a whole shard, on either side of the shard body.  The
    concatenation builds the 1026-plane slab and the crop takes back the
    1024 local planes: the shapes of the 1-D chain, which runs as the
    ``(4, 1, 1)`` process grid.  (The ``jax.jit`` wrapper only lets shape
    structs stand for the field.)"""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("i",),
                axis_types=(AxisType.Auto,))
    a = jax.ShapeDtypeStruct((4 * 2 * N, N, N), jnp.float32,
                             sharding=NamedSharding(mesh, P("i")))
    w = jax.ShapeDtypeStruct((2, 2, 2), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    compiled = jax.jit(lambda a, w: stencil_sharded(
        a, w, "stencil27", mesh=mesh, axis="i", bc="clamp",
        interpret=False)).lower(a, w).compile()
    txt = compiled.as_text()
    assert set(KERNEL_NAME.findall(txt)) == {"stencil_stream_jtiled"}
    assert txt.count(KERNEL) == 1
    assert len(re.findall(r"%collective-permute-start(?:\.\d+)? = ",
                          txt)) == 2
    shard = rf"f32\[(?:1,)?{2 * N},{N},{N}\]"
    assert not re.search(rf"= {shard}\{{[^}}]*\}} copy\(", txt)
    slab = rf"f32\[(?:1,)?{2 * N + 2},{N},{N}\]"
    assert re.search(rf"= {slab}\{{[^}}]*\}} fusion\(", txt)
    assert re.search(rf"= {shard}\{{[^}}]*\}} slice\([^)]*\), "
                     rf"slice=\{{[^}}]*\[1:{2 * N + 1}\]", txt)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 8 << 30          # ~4.15 GB: field, output, slab, kernel out
