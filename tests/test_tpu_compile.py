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
    i, each chip's 1024 planes swept by the j-tiled stream kernel, which
    takes the one exchanged plane a side as operands.  Two halo
    collective-permutes; the kernel writes the 1024 local planes, so no
    slab of 1026 planes is built and no output is cropped, and the
    program holds the field and its output alone.  The program
    ``stencil_sharded`` launches takes the caller's (M, N, P) field as it
    is and reshapes it to (1, M, N, P) and back inside, so both reshapes
    lower to bitcasts: no copy of a whole shard, on either side of the
    shard body.  These are the shapes of the 1-D chain, which runs as the
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
    assert re.search(rf"= f32\[1,{2 * N},{N},{N}\]\{{[^}}]*\}} "
                     rf"custom-call\(", txt)
    slab = rf"f32\[(?:1,)?{2 * N + 2},{N},{N}\]"
    assert not re.search(slab, txt)
    assert not re.search(rf"= {shard}\{{[^}}]*\}} slice\(", txt)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    field = 2 * N * N * N * 4
    assert used < 2 * field + (16 << 20)     # field and output: 2.15 GB


# sha256 of each Mosaic kernel module, printed without debug info, of the
# one-chip cells' calls at 1024x512x512 f32 as they lowered before the
# stream kernel learned to take exchanged i planes as operands (JAX 0.9.0;
# a JAX with another Mosaic lowering prints other modules).
ONE_CHIP_KERNELS = {
    "apply": ("stencil_stream_jtiled",
              "805485ee079432b8c513f49a4c37de70dc899454ab932f9f777467df2c3ec53d"),
    "smooth4": ("stencil_stream_jtiled",
                "c13d7639f8c31943359496b6bce32680cde118f0f13d47998aed793ee6662ec8"),
}


@pytest.mark.parametrize("cell", sorted(ONE_CHIP_KERNELS))
def test_one_chip_stream_kernel_lowers_as_before(one_chip, monkeypatch,
                                                 cell):
    """The one-chip ``j27-f32`` cells pass no exchanged planes: their
    ``pallas_call`` keeps its operands and index maps, and Mosaic prints
    its kernel exactly as it did before the i-halo operands existed."""
    import hashlib
    from jax.experimental import mosaic
    seen = []
    lower = mosaic.lower_module_to_custom_call

    def record(ctx, *args, module, kernel_name, **kw):
        seen.append((kernel_name,
                     module.operation.get_asm(enable_debug_info=False)))
        return lower(ctx, *args, module=module, kernel_name=kernel_name,
                     **kw)

    monkeypatch.setattr(mosaic, "lower_module_to_custom_call", record)
    jax.clear_caches()              # lower afresh, through the recorder
    call = (_apply(bc="clamp") if cell == "apply" else
            lambda a, w: stencil_sweep_driver(a, w, "stencil27", sweeps=4,
                                              bc="clamp", interpret=False))
    jax.jit(call).lower(*_shapes(one_chip, (2 * N, N, N), jnp.float32))
    assert [(name, hashlib.sha256(text.encode()).hexdigest())
            for name, text in seen] == [ONE_CHIP_KERNELS[cell]]
