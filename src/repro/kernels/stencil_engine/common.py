"""Shared stencil-engine plumbing: budgets, divisors, legacy Pallas helpers.

Engine-wide constants and small helpers live here so the cost model, the
block pickers, and the benchmarks agree on one source of truth:

* :data:`VMEM_LIMIT_BYTES` -- the scoped-VMEM limit every engine kernel
  compiles with, and :data:`DEFAULT_VMEM_BUDGET`, the residency budget
  every block/tile chooser defaults to, derived from it.
* :func:`divisors` -- sorted divisors of an int (block-size candidates).
* The profiler span names (``SPAN_*``) at the engine's host-path
  boundaries and the fixed ``name=`` of each kernel (``KERNEL_*``), which
  the device trace shows; :func:`annotate` attaches a span's arguments.

The rest are the original halo/tiling utilities the MXU banded-matmul
kernel still imports (``shifted_planes``, ``interior_mask``,
``stencil_pallas_call``), re-exported by ``repro.kernels._stencil_common``
for backward compatibility; the engine's own kernels live in
:mod:`.kernel`/:mod:`.ops`.
"""

from __future__ import annotations

import functools
from typing import Callable, List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# The scoped-VMEM limit (bytes) every engine pallas_call is compiled with
# (``CompilerParams(vmem_limit_bytes=...)``), in place of Mosaic's 16 MiB
# default, which refused the default 512^3 f32 path.  A pinned or fallback
# geometry the model calls infeasible (the 512x512-plane wavefront) still
# compiles under it.
VMEM_LIMIT_BYTES = 48 << 20

# The block/tile choosers' residency budget, derived from that one limit:
# the model (``autotune._fits``) itemizes the double-buffered blocks, the
# scratch windows and the working strips; the other half is headroom for
# the compiler scratch it does not itemize.
DEFAULT_VMEM_BUDGET = VMEM_LIMIT_BYTES // 2

# Profiler spans (``jax.profiler.TraceAnnotation``) at the boundaries of the
# engine's host path.  One entry span wraps each public entry call; entries
# called from inside another (the sweep driver's fused and chained modes
# call ``stencil_apply``) nest, and the outermost one is the call.
SPAN_APPLY = "stencil.apply"
SPAN_SWEEP_DRIVER = "stencil.sweep_driver"
SPAN_SHARDED = "stencil.sharded"
ENTRY_SPANS = (SPAN_APPLY, SPAN_SWEEP_DRIVER, SPAN_SHARDED)
# The host-side choice of an entry (spec, BC and guard resolution, the
# plan, the sweep mode and blocks), up to the executor call; its arguments
# carry the decision where the host makes one.  The sharded entry's also
# carry its shard geometry: ``halo`` (planes exchanged each side) and
# ``local``, the per-axis (i, j, k) triples, and ``overlap``.
SPAN_SELECT = "stencil.select"
# The call into the jitted executor until it returns: JAX's dispatch and the
# runtime's enqueue.
SPAN_LAUNCH = "stencil.launch"
# Inside the jitted bodies: it runs only while JAX traces them, so each span
# is one retrace (and compile or compile-cache load), with the decision made
# at trace time as its arguments (``halo_rows`` on j-tiled stream calls, the
# one-device and the shard bodies alike).
SPAN_TRACE = "stencil.trace"
SPANS = ENTRY_SPANS + (SPAN_SELECT, SPAN_LAUNCH, SPAN_TRACE)

# The fixed ``name=`` of each ``pallas_call`` site in :mod:`.ops`: the HLO
# instruction, and so the device trace's op, carries it.
KERNEL_STREAM = "stencil_stream"
KERNEL_STREAM_JTILED = "stencil_stream_jtiled"
KERNEL_REPLICATE = "stencil_replicate"
KERNEL_REPLICATE_JTILED = "stencil_replicate_jtiled"
KERNEL_WAVEFRONT = "stencil_wavefront"
KERNEL_STRIP = "stencil_strip"
KERNEL_ROWS = "stencil_rows"


def annotate(span: jax.profiler.TraceAnnotation, **args) -> None:
    """Attach ``args`` (those not ``None``) to the open ``span``, only while
    a profiler records: with none active this costs one check."""
    if jax.profiler.TraceAnnotation.is_enabled():
        span.set_metadata(**{k: v for k, v in args.items() if v is not None})


def divisors(x: int) -> List[int]:
    """All divisors of ``x`` in ascending order (block-size candidates)."""
    small, large = [], []
    d = 1
    while d * d <= x:
        if x % d == 0:
            small.append(d)
            if d != x // d:
                large.append(x // d)
        d += 1
    return small + large[::-1]


def shifted_planes(prev_blk: jax.Array, cur: jax.Array, nxt_blk: jax.Array):
    """Rows (i-1, i, i+1) for every row i of the current block."""
    up = jnp.concatenate([prev_blk[-1:], cur[:-1]], axis=0)
    down = jnp.concatenate([cur[1:], nxt_blk[:1]], axis=0)
    return up, cur, down


def interior_mask(bi: int, n: int, p: int, i_blk, m_total: int) -> jax.Array:
    """True on interior points of the global (M, N, P) grid for this block."""
    gi = i_blk * bi + jax.lax.broadcasted_iota(jnp.int32, (bi, n, p), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (bi, n, p), 1)
    kk = jax.lax.broadcasted_iota(jnp.int32, (bi, n, p), 2)
    return ((gi > 0) & (gi < m_total - 1)
            & (jj > 0) & (jj < n - 1)
            & (kk > 0) & (kk < p - 1))


def stencil_pallas_call(kernel_body: Callable, a: jax.Array, weights: jax.Array,
                        bi: int, interpret: bool) -> jax.Array:
    """Common pallas_call wiring: 3 shifted views of ``a`` + weights in SMEM."""
    m, n, p = a.shape
    if m % bi != 0:
        raise ValueError(f"block size {bi} must divide M={m}")
    nblk = m // bi
    block = (bi, n, p)
    grid = (nblk,)
    in_specs = [
        pl.BlockSpec(block, lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
        pl.BlockSpec(block, lambda i: (i, 0, 0)),
        pl.BlockSpec(block, functools.partial(
            lambda i, top: (jnp.minimum(i + 1, top), 0, 0), top=nblk - 1)),
        pl.BlockSpec(weights.shape, lambda i: tuple(0 for _ in weights.shape)),
    ]
    return pl.pallas_call(
        functools.partial(kernel_body, bi=bi, m_total=m),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(block, lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret,
    )(a, a, a, weights)
