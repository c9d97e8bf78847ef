"""Jitted public entry point: one configurable stencil executor.

``stencil_apply`` runs any registered (or ad-hoc) radius-R spec over batched,
multi-dtype inputs, with optional fused Jacobi sweeps, via the kernel bodies
in :mod:`.kernel`.  The spec is compiled to an execution plan (:mod:`.plan`
-- a pass pipeline; ``auto``/``factored``/``cse``/``direct`` presets) before
tracing; the volumetric hot path is the *plane-streaming* kernel
(``path="stream"``, each input plane fetched from HBM once, the
``radius * sweeps``-deep halo carried in VMEM scratch across grid steps)
with the halo-*replicated* kernel kept as a parity escape hatch
(``path="replicate"``, ``2r + 1`` neighbour views, like ``plan="direct"``);
and blocks may be tiled along j as well as i when the full N x P slab would
not fit VMEM.  See the package docstring for the full tour.
"""

from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.profiler import TraceAnnotation

from .autotune import (PATH_KINDS, autotune_blocks, autotune_engine,
                       pick_block_rows, sublane_rows)
from .common import (KERNEL_REPLICATE, KERNEL_REPLICATE_JTILED, KERNEL_ROWS,
                     KERNEL_STREAM, KERNEL_STREAM_JTILED, KERNEL_STRIP,
                     KERNEL_WAVEFRONT, SPAN_APPLY, SPAN_LAUNCH, SPAN_SELECT,
                     SPAN_TRACE, VMEM_LIMIT_BYTES, annotate)
from .kernel import (KernelFault, acc_dtype_for, stencil1d_kernel,
                     stencil3d_kernel, stencil3d_stream_kernel,
                     stencil3d_strip_kernel, stencil3d_wavefront_kernel)
from .plan import StencilPlan, compile_plan
from .spec import StencilSpec, get_stencil


# Every engine pallas_call compiles under this one scoped-VMEM limit; the
# block choosers' residency budget is derived from the same number
# (:data:`~.common.DEFAULT_VMEM_BUDGET`).
_TPU_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _periodic_axes(spec: StencilSpec):
    """(i, j) axis periodicity (periodic is validated as paired)."""
    return (spec.bc[0][0].kind == "periodic",
            spec.bc[1][0].kind == "periodic")


@functools.lru_cache(maxsize=None)
def default_interpret() -> bool:
    """Resolve ``interpret=None``: interpret the Pallas kernels only when no
    compiled backend for *these kernels* is available -- i.e. run compiled
    on TPU and interpreted elsewhere -- so the same call site works
    everywhere.  The kernel bodies are Mosaic-TPU-shaped (``pltpu.VMEM``
    scratch windows carried across a sequential grid), which the GPU
    (Triton / Mosaic-GPU) lowerings do not provide, so GPU hosts stay on
    the interpreter too.

    This is also a silent fallback: on a host whose TPU failed to
    initialise, JAX's default backend is the CPU and every kernel then runs
    interpreted there.  Code that must run on the chip checks for itself --
    ``chip_smoke.py`` asserts the platform and that each compiled program
    holds a ``tpu_custom_call``."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def _edge_index(x, d: int, nb: int, wrap: bool):
    """A neighbour block index at the domain edge: wrapped for a periodic
    axis (the halo genuinely comes from the far side), clamped otherwise
    (the duplicate data lands on positions the kernel's ghost fill /
    interior mask overwrites)."""
    if d == 0:
        return x
    return (x + d) % nb if wrap else jnp.clip(x + d, 0, nb - 1)


def _neighbor_imap(di: int, dj: int, nbi: int, nbj: int,
                   wrap_i: bool, wrap_j: bool):
    """Index map for the (di, dj) neighbour view of a (1, bi, bj, P) block
    grid, per-axis wrapped (periodic) or clamped at the domain edges."""
    def f(bb, i, j):
        return (bb, _edge_index(i, di, nbi, wrap_i),
                _edge_index(j, dj, nbj, wrap_j), 0)
    return f


def halo_block_rows(hj: int, bj: int, itemsize: int) -> int:
    """Rows of each j-halo block the j-tiled stream kernel stages beside a
    ``bj``-wide tile: the smallest multiple of the dtype's sublane tile that
    covers the ``hj``-deep halo and divides ``bj``, else ``bj`` itself (the
    neighbour tiles whole).  0 when the spec has no j halo."""
    if hj == 0:
        return 0
    sub = sublane_rows(itemsize)
    for hb in range(-(-hj // sub) * sub, bj, sub):
        if bj % hb == 0:
            return hb
    return bj


def staged_halo_rows(spec: StencilSpec, path: str, bj: Optional[int],
                     sweeps: int, itemsize: int) -> Optional[int]:
    """:func:`halo_block_rows` of a call on ``path`` with j-tile ``bj``, as
    ``stencil.trace`` spans record it: ``None`` unless the call is j-tiled
    on the stream path."""
    if path != "stream" or bj is None:
        return None
    return halo_block_rows(spec.radius[1] * sweeps * spec.sweep_apps, bj,
                           itemsize)


def _validate_blocks(m: int, n: int, bi: int, bj: Optional[int],
                     sweeps: int, radius, apps: int = 1) -> None:
    """``apps`` is the spec's applications per sweep (2 for red-black
    Gauss-Seidel) -- the carried halo is ``radius * sweeps * apps`` deep."""
    ri, rj, _ = radius
    if m % bi != 0:
        raise ValueError(f"block size {bi} must divide M={m}")
    if ri * sweeps * apps > bi:
        raise ValueError(f"fused sweeps={sweeps} exceed the carried halo; "
                         f"need block_i >= sweeps*r_i*sweep_apps "
                         f"(block_i={bi}, r_i={ri}, sweep_apps={apps})")
    if bj is not None:
        if n % bj != 0:
            raise ValueError(f"block size {bj} must divide N={n}")
        if rj * sweeps * apps > bj:
            raise ValueError(f"fused sweeps={sweeps} exceed the carried "
                             f"halo; need block_j >= sweeps*r_j*sweep_apps "
                             f"(block_j={bj}, r_j={rj}, sweep_apps={apps})")


def _call_3d_stream(a4: jax.Array, wf: jax.Array, geom: jax.Array,
                    plan: StencilPlan, bi: int, bj: Optional[int],
                    sweeps: int, interpret: bool,
                    external_i_halo: bool = False,
                    fault: Optional[KernelFault] = None,
                    ext_j: bool = False, ext_k: bool = False,
                    n_global: Optional[int] = None,
                    p_global: Optional[int] = None) -> jax.Array:
    """Wire the plane-streaming kernel: one pass over the i-blocks with one
    extra grid step, a lagged output index map, and a VMEM scratch window of
    ``bi + ri * sweeps`` input planes carried across steps.  Untiled, the
    input is a single identity-mapped operand -- each plane is fetched from
    HBM exactly once per call (the final clamped step re-presents the last
    block, which Pallas revisiting semantics keep DMA-free); j-tiled, the
    tile and two j-halo blocks of :func:`halo_block_rows` rows stream i
    within each j-tile, so a plane is fetched ``1 + 2 hb / bj`` times (vs
    the replicated path's ``(2ri+1)(2rj+1)``).

    A periodic i axis (unless ``external_i_halo`` -- the sharded ring
    already materialized the wrap) adds one more lead-in step and walks the
    wrapped block sequence ``(t + nbi - 1) % nbi``: the last block's tail
    planes are staged first (the ghost rows below row 0) and the first
    block's head planes are re-fetched at the end -- the ``r * sweeps``
    lead/tail planes are the only re-fetched HBM traffic.

    Variable-coefficient specs (``wf`` is ``(n_weights, M, N, P)``) add a
    parallel set of coefficient views under the *same* block walk plus a
    second co-rotating VMEM scratch window, so coefficient planes stream
    exactly like field planes -- fetched once per call."""
    b, m, n, p = a4.shape
    nbi = m // bi
    ri, rj, _ = plan.spec.radius
    hi = ri * sweeps * plan.spec.sweep_apps
    var = plan.spec.coef == "var"
    per_i, per_j = _periodic_axes(plan.spec)
    wrap_i = per_i and not external_i_halo and hi > 0
    steps = nbi + (2 if wrap_i else 1)
    lag = 2 if wrap_i else 1
    kern = functools.partial(stencil3d_stream_kernel, plan=plan, bi=bi,
                             bj=bj, n_global=n_global if ext_j else n,
                             sweeps=sweeps,
                             acc_dtype=acc_dtype_for(a4.dtype),
                             wrap_i=wrap_i, fault=fault, ext_j=ext_j,
                             ext_k=ext_k,
                             p_global=p_global if ext_k else None)
    if wrap_i:
        def imap_t(t):
            return (t + nbi - 1) % nbi
    else:
        def imap_t(t):
            return jnp.minimum(t, nbi - 1)

    def omap_t(t):
        return jnp.clip(t - lag, 0, nbi - 1)

    if bj is None:
        block = (1, bi, n, p)
        in_specs = [
            pl.BlockSpec(block, lambda bb, t: (bb, imap_t(t), 0, 0)),
            pl.BlockSpec(geom.shape, lambda bb, t: (0,)),
        ]
        scratch = [pltpu.VMEM((bi + hi, n, p), a4.dtype)]
        if var:
            in_specs.append(pl.BlockSpec((wf.shape[0], bi, n, p),
                                         lambda bb, t: (0, imap_t(t), 0, 0)))
            scratch.append(pltpu.VMEM((wf.shape[0], bi + hi, n, p), wf.dtype))
        else:
            in_specs.append(pl.BlockSpec(wf.shape, lambda bb, t: (0,)))
        return pl.pallas_call(
            kern,
            grid=(b, steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                block, lambda bb, t: (bb, omap_t(t), 0, 0)),
            out_shape=jax.ShapeDtypeStruct(a4.shape, a4.dtype),
            scratch_shapes=scratch,
            interpret=interpret,
            compiler_params=_TPU_PARAMS,
            name=KERNEL_STREAM,
        )(a4, geom, wf)

    hj = rj * sweeps * plan.spec.sweep_apps
    block = (1, bi, bj, p)
    # Beside the tile, only its j-halo is staged: the left block's tail and
    # the right block's head hj rows, in blocks of hb rows (sublane-aligned
    # and dividing bj; hb == bj stages the neighbour tiles whole).  Index
    # maps count in hb-row blocks; clamped edge blocks land on positions the
    # ghost fill / interior mask overwrites, periodic ones wrap.
    hb = halo_block_rows(hj, bj, a4.dtype.itemsize)

    def jmap(rows: int, d: int, lead: Optional[int]):
        """Index map of the block of ``rows`` j-rows that lies ``d`` such
        blocks past the first row of tile ``j``."""
        def f(bb, j, t):
            return (bb if lead is None else lead, imap_t(t),
                    _edge_index(j * (bj // rows), d, n // rows, per_j), 0)
        return f

    def views(lead_shape, lead=None):
        """Specs of (left halo, tile, right halo), or of the tile alone."""
        def view(rows, d):
            return pl.BlockSpec(lead_shape + (rows, p), jmap(rows, d, lead))
        if not hj:
            return [view(bj, 0)]
        return [view(hb, -1), view(bj, 0), view(hb, bj // hb)]

    in_specs = views((1, bi))
    n_views = len(in_specs)
    in_specs += [pl.BlockSpec(geom.shape, lambda bb, j, t: (0,))]
    scratch = [pltpu.VMEM((bi + hi, bj + 2 * hj, p), a4.dtype)]
    if var:
        in_specs += views((wf.shape[0], bi), lead=0)
        scratch.append(pltpu.VMEM((wf.shape[0], bi + hi, bj + 2 * hj, p),
                                  wf.dtype))
        w_args = [wf] * n_views
    else:
        in_specs += [pl.BlockSpec(wf.shape, lambda bb, j, t: (0,))]
        w_args = [wf]
    return pl.pallas_call(
        kern,
        grid=(b, n // bj, steps),      # i innermost: the stream restarts
        in_specs=in_specs,             # (and re-primes) per j-tile
        out_specs=pl.BlockSpec(
            block, lambda bb, j, t: (bb, omap_t(t), j, 0)),
        out_shape=jax.ShapeDtypeStruct(a4.shape, a4.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=_TPU_PARAMS,
        name=KERNEL_STREAM_JTILED,
    )(*([a4] * n_views), geom, *w_args)


def call_3d(a4: jax.Array, wf: jax.Array, geom: jax.Array, plan: StencilPlan,
            bi: int, bj: Optional[int], sweeps: int, interpret: bool,
            path: str = "stream", external_i_halo: bool = False,
            fault: Optional[KernelFault] = None,
            ext_j: bool = False, ext_k: bool = False,
            n_global: Optional[int] = None,
            p_global: Optional[int] = None) -> jax.Array:
    """Wire a fused volumetric kernel: ``a4`` is ``(B, M, N, P)``.

    ``path="stream"`` (default) walks the i-blocks in order and carries the
    halo in VMEM scratch -- each input plane is fetched once.
    ``path="replicate"`` is the stateless parity escape hatch: the i-halo
    comes from passing ``a4`` ``2ri + 1`` times under block-shifted
    index maps (untiled) or the full ``(2ri+1) x (2rj+1)``
    neighbour views (j-tiled) -- edge blocks clamp, except on periodic axes
    where they wrap to the far side.  Both paths share block geometry:
    untiled blocks are ``(1, bi, N, P)``; j-tiled blocks ``(1, bi, bj, P)``,
    so the working slab never exceeds ``(bi + 2*hi)(bj + 2*hj)P`` whatever
    N is (``h = radius * sweeps``).  ``geom`` = (global row offset, global
    M) int32.  ``external_i_halo=True`` (the sharded path) marks the i-axis
    halo as already materialized in ``a4`` -- a periodic i BC is then *not*
    wrapped locally (the ring exchange supplied the wrapped rows).

    ``ext_j``/``ext_k`` (the multi-axis-sharded path) mark the j/k ghosts
    as externally materialized too: ``a4`` is the per-shard slab already
    extended along those axes, ``geom`` grows to ``(gi0, M, j0, k0)``, and
    the kernels realize the j/k BCs at the *global* edges from
    ``n_global``/``p_global`` (the global N/P).  External j is
    incompatible with j-tiling (the tile walk would re-wrap the exchanged
    columns), so ``bj`` must be ``None``.
    """
    b, m, n, p = a4.shape
    if (ext_j or ext_k) and bj is not None:
        raise ValueError("call_3d: block_j tiling is incompatible with an "
                         "externally materialized j/k halo (ext_j/ext_k); "
                         "pass block_j=None on j/k-sharded slabs")
    _validate_blocks(m, n, bi, bj, sweeps, plan.spec.radius,
                     plan.spec.sweep_apps)
    if path == "stream":
        return _call_3d_stream(a4, wf, geom, plan, bi, bj, sweeps, interpret,
                               external_i_halo, fault, ext_j=ext_j,
                               ext_k=ext_k, n_global=n_global,
                               p_global=p_global)
    if path != "replicate":
        raise ValueError(f"unknown path {path!r}; expected 'stream' or "
                         f"'replicate'")
    nbi = m // bi
    ri, rj, _ = plan.spec.radius
    var = plan.spec.coef == "var"
    per_i, per_j = _periodic_axes(plan.spec)
    wrap_i = per_i and not external_i_halo
    kern = functools.partial(stencil3d_kernel, plan=plan, bi=bi, bj=bj,
                             n_global=n_global if ext_j else n,
                             sweeps=sweeps,
                             acc_dtype=acc_dtype_for(a4.dtype),
                             ext_j=ext_j, ext_k=ext_k,
                             p_global=p_global if ext_k else None)
    if bj is None:
        block = (1, bi, n, p)

        def imap_i(di: int, lead: Optional[int] = None):
            def f(bb, i):
                return (bb if lead is None else lead,
                        _edge_index(i, di, nbi, wrap_i), 0, 0)
            return f

        # 2ri+1 staged views = the replicated path's canonical per-radius
        # cost ((2ri+2) bytes/pt -- what makes the stream-vs-replicate race
        # honest); only the +-1 views' halo slices are read by the body.
        in_specs = [pl.BlockSpec(block, imap_i(di))
                    for di in range(-ri, ri + 1)]
        in_specs += [pl.BlockSpec(geom.shape, lambda bb, i: (0,))]
        if var:
            # a full parallel set of coefficient views under the same walk
            in_specs += [pl.BlockSpec((wf.shape[0], bi, n, p),
                                      imap_i(di, lead=0))
                         for di in range(-ri, ri + 1)]
            w_args = [wf] * (2 * ri + 1)
        else:
            in_specs += [pl.BlockSpec(wf.shape, lambda bb, i: (0,))]
            w_args = [wf]
        return pl.pallas_call(
            kern,
            grid=(b, nbi),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(block, lambda bb, i: (bb, i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct(a4.shape, a4.dtype),
            interpret=interpret,
            compiler_params=_TPU_PARAMS,
            name=KERNEL_REPLICATE,
        )(*([a4] * (2 * ri + 1)), geom, *w_args)

    nbj = n // bj
    block = (1, bi, bj, p)
    in_specs = [pl.BlockSpec(block,
                             _neighbor_imap(di, dj, nbi, nbj, wrap_i, per_j))
                for di in range(-ri, ri + 1) for dj in range(-rj, rj + 1)]
    in_specs += [pl.BlockSpec(geom.shape, lambda bb, i, j: (0,))]
    n_views = (2 * ri + 1) * (2 * rj + 1)
    if var:
        def wmap(di: int, dj: int):
            inner = _neighbor_imap(di, dj, nbi, nbj, wrap_i, per_j)

            def f(bb, i, j):
                return (0,) + inner(bb, i, j)[1:]
            return f

        in_specs += [pl.BlockSpec((wf.shape[0], bi, bj, p), wmap(di, dj))
                     for di in range(-ri, ri + 1)
                     for dj in range(-rj, rj + 1)]
        w_args = [wf] * n_views
    else:
        in_specs += [pl.BlockSpec(wf.shape, lambda bb, i, j: (0,))]
        w_args = [wf]
    return pl.pallas_call(
        kern,
        grid=(b, nbi, nbj),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(block, lambda bb, i, j: (bb, i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(a4.shape, a4.dtype),
        interpret=interpret,
        compiler_params=_TPU_PARAMS,
        name=KERNEL_REPLICATE_JTILED,
    )(*([a4] * n_views), geom, *w_args)


def call_3d_wavefront(a4: jax.Array, wf: jax.Array, geom: jax.Array,
                      plan: StencilPlan, bi: int, sweeps: int,
                      interpret: bool, ext_j: bool = False,
                      ext_k: bool = False, n_global: Optional[int] = None,
                      p_global: Optional[int] = None) -> jax.Array:
    """Wire the temporal-wavefront kernel: ``sweeps`` pipelined sweep stages
    ride one pass over the i-blocks on a grid of ``nbi + sweeps`` steps with
    an ``s``-lagged output map, so each input plane is fetched from HBM once
    per ``sweeps`` applications (~``2 / sweeps`` transfers per point) while
    every stage carries only the *single-sweep* halo ``ha = radius *
    sweep_apps`` in its rotating VMEM window -- ``sweeps`` windows of
    ``bi + ha`` planes (stage 1 in the input dtype, later stages in the
    accumulation dtype) instead of the fused path's one ``bi + radius *
    sweeps * sweep_apps`` window and matching VPU-redundant strip.

    Untiled (full-N blocks), constant coefficients only.  A periodic i axis
    must arrive pre-extended (``radius * sweep_apps * sweeps`` wrapped rows
    per side + external-halo ``geom``); :func:`~.sweeps.stencil_wavefront`
    and the sharded deep-halo exchange both do exactly that.
    """
    b, m, n, p = a4.shape
    spec = plan.spec
    if spec.coef == "var":
        raise ValueError(
            f"{spec.name}: the wavefront path needs constant coefficients "
            f"(variable-coefficient planes would need an s-block-deep "
            f"window); use the fused or chained mode")
    ri = spec.radius[0]
    ha = ri * spec.sweep_apps
    if m % bi != 0:
        raise ValueError(f"wavefront block size {bi} must divide M={m}")
    if ha > bi:
        raise ValueError(f"wavefront needs block_i >= radius*sweep_apps "
                         f"(block_i={bi}, r_i={ri}, "
                         f"sweep_apps={spec.sweep_apps})")
    nbi = m // bi
    s = sweeps
    acc = acc_dtype_for(a4.dtype)
    kern = functools.partial(stencil3d_wavefront_kernel, plan=plan, bi=bi,
                             n_global=n_global if ext_j else n, sweeps=s,
                             acc_dtype=acc, ext_j=ext_j, ext_k=ext_k,
                             p_global=p_global if ext_k else None)
    block = (1, bi, n, p)
    in_specs = [
        pl.BlockSpec(block, lambda bb, t: (bb, jnp.minimum(t, nbi - 1), 0, 0)),
        pl.BlockSpec(geom.shape, lambda bb, t: (0,)),
        pl.BlockSpec(wf.shape, lambda bb, t: (0,)),
    ]
    scratch = [pltpu.VMEM((bi + ha, n, p), a4.dtype)]
    scratch += [pltpu.VMEM((bi + ha, n, p), acc) for _ in range(s - 1)]
    return pl.pallas_call(
        kern,
        grid=(b, nbi + s),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            block, lambda bb, t: (bb, jnp.clip(t - s, 0, nbi - 1), 0, 0)),
        out_shape=jax.ShapeDtypeStruct(a4.shape, a4.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=_TPU_PARAMS,
        name=KERNEL_WAVEFRONT,
    )(a4, geom, wf)


def call_3d_strip(a4: jax.Array, wf: jax.Array, geom: jax.Array,
                  plan: StencilPlan, sweeps: int, interpret: bool, h: int,
                  ext_j: bool = False, ext_k: bool = False,
                  n_global: Optional[int] = None,
                  p_global: Optional[int] = None) -> jax.Array:
    """Wire the boundary-strip kernel for the overlap executor: ``a4`` is
    ``(B, rows, N, P)`` with ``rows = out_rows + 2h`` i-planes that already
    include the ``h`` exchanged ghost planes per side (``h = radius *
    sweeps * sweep_apps``).  One identity-mapped block per batch entry --
    the strip is thin by construction (``3h`` planes for the overlap
    executor's edge strips), so no streaming window or neighbour views are
    staged.  Returns the central ``(B, rows - 2h, N, P)`` planes.  On a
    variable-coefficient spec ``wf`` is the matching pre-extended
    ``(n_weights, rows, N, P)`` coefficient strip."""
    b, rows, n, p = a4.shape
    if rows <= 2 * h:
        raise ValueError(f"call_3d_strip: strip of {rows} planes has no "
                         f"interior under the {h}-plane halo")
    kern = functools.partial(stencil3d_strip_kernel, plan=plan, h=h,
                             n_global=n_global if ext_j else n,
                             sweeps=sweeps,
                             acc_dtype=acc_dtype_for(a4.dtype),
                             ext_j=ext_j, ext_k=ext_k,
                             p_global=p_global if ext_k else None)
    in_specs = [
        pl.BlockSpec((1, rows, n, p), lambda bb: (bb, 0, 0, 0)),
        pl.BlockSpec(geom.shape, lambda bb: (0,)),
        pl.BlockSpec(wf.shape, lambda bb: (0,) * wf.ndim),
    ]
    return pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows - 2 * h, n, p),
                               lambda bb: (bb, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, rows - 2 * h, n, p), a4.dtype),
        interpret=interpret,
        compiler_params=_TPU_PARAMS,
        name=KERNEL_STRIP,
    )(a4, geom, wf)


def _call_1d(a2: jax.Array, wf: jax.Array, plan: StencilPlan, block_rows: int,
             sweeps: int, interpret: bool) -> jax.Array:
    rows, p = a2.shape
    if rows % block_rows != 0:
        raise ValueError(f"block_rows {block_rows} must divide rows={rows}")
    return pl.pallas_call(
        functools.partial(stencil1d_kernel, plan=plan, sweeps=sweeps,
                          acc_dtype=acc_dtype_for(a2.dtype)),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, p), lambda i: (i, 0)),
                  pl.BlockSpec(wf.shape,
                               lambda i: (0,) * wf.ndim)],
        out_specs=pl.BlockSpec((block_rows, p), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(a2.shape, a2.dtype),
        interpret=interpret,
        compiler_params=_TPU_PARAMS,
        name=KERNEL_ROWS,
    )(a2, wf)


def _stencil_apply_impl(a: jax.Array, w: jax.Array,
                        stencil: Union[str, int, StencilSpec] = "stencil27",
                        block_i: Optional[int] = None,
                        block_j: Optional[int] = None, plan: str = "auto",
                        sweeps: int = 1, path: str = "auto", bc=None,
                        interpret: Optional[bool] = None,
                        _fault: Optional[KernelFault] = None) -> jax.Array:
    """The jittable body of :func:`stencil_apply` (see its docstring).

    ``_fault`` is the static in-kernel fault-injection descriptor
    (:class:`~.kernel.KernelFault`; tests only) -- ``None``, the default,
    traces the byte-identical historical program."""
    with TraceAnnotation(SPAN_TRACE) as span:
        if sweeps < 1:
            raise ValueError(f"sweeps must be >= 1, got {sweeps}")
        if path not in PATH_KINDS:
            raise ValueError(f"unknown path {path!r}; expected one of "
                             f"{PATH_KINDS}")
        spec = get_stencil(stencil)
        if spec.guard != "off":
            # The guard never reaches the traced program: strip it so
            # guarded and unguarded calls share plan and jit caches.
            spec = spec.with_guard("off")
        if bc is not None:
            spec = spec.with_bc(bc)
        cplan = compile_plan(spec, plan)
        acc = acc_dtype_for(a.dtype)
        var = spec.coef == "var"
        interp = resolve_interpret(interpret)

        if spec.ndim == 1:
            if a.ndim < 2:
                raise ValueError(f"{spec.name}: need (..., rows, P), got "
                                 f"{a.shape}")
            wf = spec.canon_weights(w, a.shape[-1:] if var else None
                                    ).astype(acc)
            rows = int(np.prod(a.shape[:-1]))
            a2 = a.reshape(rows, a.shape[-1])
            br = block_i or pick_block_rows(rows, a.shape[-1],
                                            a.dtype.itemsize)
            annotate(span, path="rows", block_i=br, plan=cplan.kind,
                     sweeps=sweeps)
            return _call_1d(a2, wf, cplan, br, sweeps, interp
                            ).reshape(a.shape)

        if a.ndim < 3:
            raise ValueError(f"{spec.name}: need (..., M, N, P), got "
                             f"{a.shape}")
        m, n, p = a.shape[-3:]
        wf = spec.canon_weights(w, (m, n, p) if var else None).astype(acc)
        batch = int(np.prod(a.shape[:-3])) if a.ndim > 3 else 1
        a4 = a.reshape(batch, m, n, p)
        bi, bj, rpath = block_i, block_j, path
        if bi is None:
            rpath, bi, bj_auto = autotune_engine(m, n, p, a.dtype.itemsize,
                                                 sweeps=sweeps, plan=cplan,
                                                 block_j=bj, path=path)
            bj = bj if bj is not None else bj_auto
        elif rpath == "auto":
            rpath = "stream"        # pinned blocks: stream is strictly
        geom = jnp.array([0, m], jnp.int32)  # fewer HBM bytes at equal blocks
        annotate(span, path=rpath, block_i=bi, block_j=bj, plan=cplan.kind,
                 sweeps=sweeps, halo_rows=staged_halo_rows(
                     spec, rpath, bj, sweeps, a.dtype.itemsize))
        out = call_3d(a4, wf, geom, cplan, bi, bj, sweeps, interp, rpath,
                      fault=_fault)
        return out.reshape(a.shape)


stencil_apply_jit = jax.jit(
    _stencil_apply_impl,
    static_argnames=("stencil", "block_i", "block_j", "plan", "sweeps",
                     "path", "bc", "interpret", "_fault"))
"""The jitted unguarded executor -- exactly the historical ``stencil_apply``
program (``_fault=None`` adds nothing to the trace); the guarded wrapper and
the degradation ladder call this."""


def stencil_apply(a: jax.Array, w: jax.Array,
                  stencil: Union[str, int, StencilSpec] = "stencil27",
                  block_i: Optional[int] = None,
                  block_j: Optional[int] = None, plan: str = "auto",
                  sweeps: int = 1, path: str = "auto", bc=None,
                  interpret: Optional[bool] = None,
                  guard=None) -> jax.Array:
    """Apply a registered stencil: ``sweeps`` fused Jacobi applications.

    * volumetric specs: ``a`` is ``(..., M, N, P)`` -- leading dims batch;
    * k-only specs: ``a`` is ``(..., P)`` -- leading dims are rows;
    * variable-coefficient specs (``spec.coef == "var"``): ``w`` carries a
      leading ``(n_weights,)`` axis with trailing dims broadcast over the
      domain (``out[x] = sum_t w_t(x) * u[x + off_t]``, coefficients
      evaluated at the output point); the coefficient planes ride the same
      staging as the field -- co-streamed through a second VMEM rotating
      window on the streaming path, replicated views on the other;
    * bf16/f32 inputs accumulate in f32, f64 stays f64 (reference path);
    * ``plan`` picks the execution schedule (``auto`` -> ``factored`` for
      mirror-symmetric specs, ``cse`` otherwise; ``direct`` is the naive
      parity escape hatch) -- same-plan runs execute the identical op walk
      as :func:`stencil_ref` (f64 bit-parity on the reference
      configurations; exact blocking-invariance on integer-valued data --
      see :mod:`.plan` on fma contraction);
    * ``path`` picks the data-movement strategy for volumetric specs:
      ``"stream"`` fetches each input plane from HBM once and carries the
      ``radius * sweeps``-deep halo in VMEM scratch across grid steps (the
      paper's plane-streaming ideal, ~2 transfers per point at any radius);
      ``"replicate"`` re-fetches the ``2r + 1`` halo neighbours per block
      (the parity escape hatch).  ``"auto"`` streams whenever feasible,
      falling back to the replicated roofline choice per shape;
    * ``block_i``/``block_j`` (i-block rows / j-tile columns) default to the
      plan-, path-, and radius-aware cost model, which engages j-tiling
      only when the full N x P slab would blow the VMEM budget;
    * ``bc`` overrides the spec's per-axis-side boundary conditions (any
      :func:`~.spec.as_boundary` spelling -- a kind string, a
      :class:`~.spec.BC` / :func:`~.spec.dirichlet` value, or 3 per-axis
      entries, each optionally a ``(lo, hi)`` pair; hashable forms only,
      it rides through jit as a static argument).  ``None`` keeps the
      spec's own BCs (all-clamp for the plain builtins);
    * ``interpret=None`` (default) interprets the kernel only when no
      compiled Pallas backend exists for the platform (CPU/CI) and compiles
      on TPU (the kernels are Mosaic-TPU-shaped; GPU stays interpreted);
      pass an explicit bool to force either mode;
    * ``guard`` selects runtime verification + the degradation ladder
      (:mod:`.guard`): ``None`` defers to the spec's own ``guard`` field
      (``"off"`` for every builtin -- this call then *is* the historical
      jitted program, byte-identical); a :data:`~.spec.GUARD_KINDS` string
      or a :class:`~.guard.GuardPolicy` runs the checks on the result and,
      on a detected failure or a raised kernel error, retries then walks
      fused -> chained -> stream -> replicate -> oracle, returning the
      first verified result (see ``last_guard_report()``).
    """
    with TraceAnnotation(SPAN_APPLY):
        with TraceAnnotation(SPAN_SELECT):
            # The path and blocks are chosen while the executor traces
            # (``stencil.trace``); the host resolves the spec and guard.
            spec = get_stencil(stencil)
            policy_src = spec.guard if guard is None else guard
            policy = None
            if policy_src is not None and policy_src != "off":
                from .guard import as_guard
                policy = as_guard(policy_src)  # None: e.g. guard="off"
        if policy is None:
            with TraceAnnotation(SPAN_LAUNCH):
                return stencil_apply_jit(a, w, stencil, block_i=block_i,
                                         block_j=block_j, plan=plan,
                                         sweeps=sweeps, path=path, bc=bc,
                                         interpret=interpret)
        from .guard import guarded_apply
        if bc is not None:
            spec = spec.with_bc(bc)
        return guarded_apply(a, w, spec, policy, block_i=block_i,
                             block_j=block_j, plan=plan, sweeps=sweeps,
                             path=path, interpret=interpret)
