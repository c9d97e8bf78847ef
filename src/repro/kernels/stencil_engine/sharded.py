"""Multi-device stencil execution: shard_map over a process grid + halo
exchange.

The partition plan comes from ``repro.sharding.planner.stencil_grid_sharding``
(per-axis divisibility and halo-depth checks, PlanNote audit trail).  Each
sharded domain axis (i, j, k) is split into contiguous slabs, and each shard
trades ``radius * sweeps`` ghost planes a side with its neighbours on that
axis via ``lax.ppermute``; the 1-D i-chain is the ``(p, 1, 1)`` grid, with
j and k whole.  The exchange topology follows each axis's boundary
condition: a *chain* for the non-periodic BCs (edge shards receive zeros,
which the kernel's global-geometry ghost fill then turns into the clamp /
dirichlet / neumann boundary -- so those BCs materialize only on the
boundary shards) or a closed *ring* for periodic (shard 0 and shard N-1
trade wrap-around halos).  Each shard then runs the *same* fused
plan-compiled Pallas kernel as the single-device path -- by default the
plane-streaming body, so the shard_map body also fetches each local plane
from HBM exactly once and carries the halo window in VMEM scratch
(``path="replicate"`` stays available as the parity escape hatch, and
j-tiled blocking engages when the local N x P slab exceeds the VMEM
budget); the kernel's geometry operand (global row offset, global M, and
the j/k offsets where those axes are sharded) keeps the interior/boundary
masking correct across shard seams.

The compiled shard_map program is memoized in a small bounded cache keyed on
the mesh's *device ids + topology + axis names* (plus the execution
geometry), not on the ``Mesh`` object itself -- equal test meshes share one
entry and the cache can never retain more than ``_SHARDED_CACHE_MAX``
programs (the old ``lru_cache`` keyed on ``Mesh`` kept up to 64 meshes alive
indefinitely).
"""
from __future__ import annotations

import functools
import math
from collections import OrderedDict
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, PartitionSpec as P

from ...sharding.planner import StencilGridPlan, stencil_grid_sharding
from .autotune import (PATH_KINDS, autotune_engine, autotune_sweeps,
                       exchange_bytes_per_point, wavefront_block_i)
from .common import (SPAN_LAUNCH, SPAN_SELECT, SPAN_SHARDED, SPAN_TRACE,
                     annotate)
from .kernel import acc_dtype_for
from .ops import (call_3d, call_3d_strip, call_3d_wavefront,
                  resolve_interpret, staged_halo_rows, stencil_apply)
from .plan import StencilPlan, compile_plan
from .spec import StencilSpec, get_stencil

_SHARDED_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_SHARDED_CACHE_MAX = 32

# Fault injection (tests): a callable (lo, hi, axis="i") -> (lo, hi) applied
# to the ppermute'd halo slabs inside the traced shard_map body -- the fault
# lives in the exchanged data itself, exactly where a real link corruption
# would; ``axis`` names which domain axis's exchange ("i"/"j"/"k") carried
# the slabs, so per-axis faults can target one face of the process grid.
# The version counter rides the program cache key so installing/clearing a
# fault always retraces instead of reusing a clean (or faulty) program.
_HALO_FAULT = [None]
_HALO_FAULT_VERSION = [0]


def set_halo_fault(fn) -> None:
    """Install (or clear, with ``None``) the halo-exchange fault hook.
    Only :mod:`.faults` calls this."""
    _HALO_FAULT[0] = fn
    _HALO_FAULT_VERSION[0] += 1


def _mesh_key(mesh: Mesh) -> tuple:
    """Hashable mesh identity that does not retain the Mesh object: device
    platforms + ids (ids restart at 0 per backend), topology shape, and axis
    names."""
    return (tuple((d.platform, int(d.id)) for d in mesh.devices.flat),
            tuple(mesh.devices.shape), tuple(mesh.axis_names))


def _traced(body: Callable, cplan: StencilPlan, **args) -> Callable:
    """The shard_map body ``body`` inside the ``stencil.trace`` span, which
    records the trace-time decision ``args`` and, on a j-tiled stream call,
    ``halo_rows`` for the traced field's dtype: it runs once per trace."""
    def local_fn(a_loc: jax.Array, wf_: jax.Array) -> jax.Array:
        with TraceAnnotation(SPAN_TRACE) as span:
            annotate(span, halo_rows=staged_halo_rows(
                cplan.spec, args["path"], args["block_j"], args["sweeps"],
                a_loc.dtype.itemsize), **args)
            return body(a_loc, wf_)
    return local_fn


def _cache_program(key: tuple, body: Callable) -> Callable:
    """Jit, cache under ``key`` and return the program around the
    shard_map ``body``.  The program takes the caller's ``(..., M, N, P)``
    field as it is: it flattens the batch dimensions to the body's
    ``(batch, M, N, P)`` and reshapes the result back inside the one
    compile, where both reshapes lower to bitcasts.  Run eagerly, each
    would be an executable of its own that copies the whole shard."""
    def program(a: jax.Array, wf: jax.Array) -> jax.Array:
        batch = math.prod(a.shape[:-3])
        return body(a.reshape(batch, *a.shape[-3:]), wf).reshape(a.shape)

    fn = _SHARDED_CACHE[key] = jax.jit(program)
    while len(_SHARDED_CACHE) > _SHARDED_CACHE_MAX:
        _SHARDED_CACHE.popitem(last=False)
    return fn


_AXIS_LABEL = ("i", "j", "k")


def _grid_sharded_fn(cplan: StencilPlan, mesh: Mesh, names, bi: int,
                     bj: Optional[int], sweeps: int, interpret: bool,
                     halos, locs, nsh, gshape, part, path: str = "stream",
                     mode: str = "fused", overlap: str = "off"):
    """Build (and cache) the jitted shard_map program for an (pi, pj, pk)
    process grid.

    ``names`` is the per-domain-axis mesh-axis triple (``None`` = axis
    whole); ``halos`` / ``locs`` / ``nsh`` the per-axis deep halo, local
    extent and shard count; ``gshape`` the global (M, N, P).  Face ghosts
    are exchanged one axis at a time on the *progressively extended* slab
    -- j first, then k (whose face slabs already carry the j ghost
    columns), then i -- so corner and edge ghosts arrive transitively and
    no diagonal sends exist; i goes last so its slabs carry the complete
    j/k ghost columns and, under ``overlap="on"``, its ppermutes are the
    only ones the interior compute has to hide.

    ``overlap="off"`` (the serialized, bit-exact escape hatch) runs one
    kernel call on the fully extended slab.  ``overlap="on"`` splits the
    i-axis work: the two i ghost-slab ppermutes are issued with no
    consumer between them and the interior :func:`~.ops.call_3d` (which
    reads only resident planes and discards its ``h`` edge rows), leaving
    XLA free to run the collectives concurrently with the interior sweep;
    the two ``h``-deep boundary strips are then swept from the arrived
    slabs by :func:`~.ops.call_3d_strip` (``3h`` pre-extended planes
    each) and concatenated around the cropped interior."""
    key = ("grid", cplan, _mesh_key(mesh), tuple(names), bi, bj, sweeps,
           interpret, tuple(halos), tuple(locs), tuple(nsh), tuple(gshape),
           part, path, mode, overlap, _HALO_FAULT_VERSION[0])
    fn = _SHARDED_CACHE.get(key)
    if fn is not None:
        _SHARDED_CACHE.move_to_end(key)
        return fn
    var = cplan.spec.coef == "var"
    m_gl, n_gl, p_gl = gshape
    # effective halo: only sharded axes carry exchanged ghost planes
    hs = tuple(halos[d] if names[d] is not None else 0 for d in range(3))
    ext_i, ext_j, ext_k = (names[d] is not None for d in range(3))
    perms = []
    for d in range(3):
        n = nsh[d]
        if cplan.spec.bc[d][0].kind == "periodic":
            perms.append(([(i, (i + 1) % n) for i in range(n)],
                          [((i + 1) % n, i) for i in range(n)]))
        else:
            perms.append(([(i, i + 1) for i in range(n - 1)],
                          [(i + 1, i) for i in range(n - 1)]))

    def _pperm_pair(x: jax.Array, d: int):
        # ghost face slabs of domain axis d; the array axis is d + 1 for
        # the batched field (lead = batch) and the canonicalized
        # coefficient stack (lead = n_weights) alike
        ax, h = d + 1, hs[d]
        tail = jax.lax.slice_in_dim(x, x.shape[ax] - h, x.shape[ax],
                                    axis=ax)
        head = jax.lax.slice_in_dim(x, 0, h, axis=ax)
        lo = jax.lax.ppermute(tail, names[d], perms[d][0])
        hi = jax.lax.ppermute(head, names[d], perms[d][1])
        if _HALO_FAULT[0] is not None:
            lo, hi = _HALO_FAULT[0](lo, hi, axis=_AXIS_LABEL[d])
        return lo, hi

    def _exchange(x: jax.Array, d: int) -> jax.Array:
        lo, hi = _pperm_pair(x, d)
        return jnp.concatenate([lo, x, hi], axis=d + 1)

    def _offsets():
        return [jax.lax.axis_index(names[d]) * locs[d]
                if names[d] is not None else jnp.int32(0) for d in range(3)]

    def _geom(i_row, offs):
        # (gi0, M), and the j/k offsets only where a j/k halo is exchanged
        jk = [offs[1] - hs[1], offs[2] - hs[2]] if ext_j or ext_k else []
        return jnp.stack([i_row, jnp.int32(m_gl), *jk]).astype(jnp.int32)

    def local_serial(a_loc: jax.Array, wf_: jax.Array) -> jax.Array:
        offs = _offsets()
        ext, wx = a_loc, wf_
        for d in (1, 2, 0):         # j, then k, then i: transitive corners
            if names[d] is not None and hs[d] > 0:
                ext = _exchange(ext, d)
                if var:
                    wx = _exchange(wx, d)
        geom = _geom(offs[0] - hs[0], offs)
        if mode == "wavefront":
            out = call_3d_wavefront(ext, wx, geom, cplan, bi, sweeps,
                                    interpret, ext_j=ext_j, ext_k=ext_k,
                                    n_global=n_gl, p_global=p_gl)
        else:
            out = call_3d(ext, wx, geom, cplan, bi, bj, sweeps, interpret,
                          path, external_i_halo=ext_i, ext_j=ext_j,
                          ext_k=ext_k, n_global=n_gl, p_global=p_gl)
        return out[:, hs[0]:hs[0] + locs[0], hs[1]:hs[1] + locs[1],
                   hs[2]:hs[2] + locs[2]]

    h = hs[0]
    m_l = locs[0]

    def local_overlap(a_loc: jax.Array, wf_: jax.Array) -> jax.Array:
        offs = _offsets()
        ext, wx = a_loc, wf_
        for d in (1, 2):
            if names[d] is not None and hs[d] > 0:
                ext = _exchange(ext, d)
                if var:
                    wx = _exchange(wx, d)
        # Launch the i ghost-slab ppermutes now; the interior call below
        # has no data dependency on them, so the collectives and the
        # interior sweep can be scheduled concurrently.
        lo, hi = _pperm_pair(ext, 0)
        if var:
            wlo, whi = _pperm_pair(wx, 0)
        # Interior: the whole resident i extent with zero ghosts -- its
        # first/last h output rows are garbage and are replaced by the
        # strips; rows [h, m_l - h) are >= h planes from the slab edge and
        # therefore exact under the deep halo.
        interior = call_3d(ext, wx, _geom(offs[0], offs), cplan, bi, None,
                           sweeps, interpret, path, external_i_halo=True,
                           ext_j=ext_j, ext_k=ext_k, n_global=n_gl,
                           p_global=p_gl)
        strip_lo_in = jnp.concatenate([lo, ext[:, :2 * h]], axis=1)
        strip_hi_in = jnp.concatenate([ext[:, -2 * h:], hi], axis=1)
        w_lo = w_hi = wx
        if var:
            w_lo = jnp.concatenate([wlo, wx[:, :2 * h]], axis=1)
            w_hi = jnp.concatenate([wx[:, -2 * h:], whi], axis=1)
        strip_lo = call_3d_strip(strip_lo_in, w_lo,
                                 _geom(offs[0] - h, offs), cplan, sweeps,
                                 interpret, h, ext_j=ext_j, ext_k=ext_k,
                                 n_global=n_gl, p_global=p_gl)
        strip_hi = call_3d_strip(strip_hi_in, w_hi,
                                 _geom(offs[0] + m_l - 2 * h, offs), cplan,
                                 sweeps, interpret, h, ext_j=ext_j,
                                 ext_k=ext_k, n_global=n_gl, p_global=p_gl)
        out = jnp.concatenate(
            [strip_lo, interior[:, h:m_l - h], strip_hi], axis=1)
        return out[:, :, hs[1]:hs[1] + locs[1], hs[2]:hs[2] + locs[2]]

    local_fn = local_overlap if overlap == "on" else local_serial
    w_spec = part if var else P(None)
    return _cache_program(key, jax.shard_map(
        _traced(local_fn, cplan, mode=mode, path=path, block_i=bi,
                block_j=bj, plan=cplan.kind, sweeps=sweeps, overlap=overlap),
        mesh=mesh, in_specs=(part, w_spec), out_specs=part,
        check_vma=False))


def _select_grid(span, a: jax.Array, w: jax.Array, spec: StencilSpec,
                 cplan: StencilPlan, mesh: Mesh, gaxes,
                 grid_plan: Optional[StencilGridPlan],
                 block_i: Optional[int], block_j: Optional[int],
                 plan_kind: str, sweeps: int, path: str, mode: str,
                 overlap: str, interpret: bool) -> Callable[[], jax.Array]:
    """Plan and tune a :func:`stencil_sharded` call inside the
    ``stencil.select`` ``span``; return the call that runs it.  Split out
    to keep the entry point readable.  ``gaxes`` is the resolved (ai, aj,
    ak) triple (``(axis, None, None)`` for the 1-D i-chain), ``grid_plan``
    a caller-supplied :class:`StencilGridPlan` or ``None`` (plan here)."""
    m, n, p = a.shape[-3:]
    apps = spec.sweep_apps
    per = tuple(spec.bc[d][0].kind == "periodic" for d in range(3))
    if mode == "wavefront" and overlap == "on":
        raise ValueError(f"{spec.name}: overlap='on' needs the fused mode "
                         f"(the wavefront pipeline consumes its deep halo "
                         f"up front, leaving no interior to overlap); use "
                         f"overlap='off' or mode='fused'")
    if grid_plan is None:
        grid_plan = stencil_grid_sharding((m, n, p), mesh, axes=gaxes,
                                          sweeps=sweeps * apps,
                                          radius=spec.radius, periodic=per)
    else:
        for d in range(3):
            need = spec.radius[d] * sweeps * apps
            if grid_plan.n_shards[d] > 1 and grid_plan.halo[d] < need:
                raise ValueError(
                    f"grid_plan.halo[{d}]={grid_plan.halo[d]} planes/side "
                    f"cannot cover radius {spec.radius[d]} x sweeps "
                    f"{sweeps} x sweep_apps {apps} = {need}; re-plan with "
                    f"stencil_grid_sharding(..., sweeps={sweeps * apps})")
    if grid_plan.total_shards <= 1:
        # every axis fell back: same single-device fallback as the 1-D path
        return _fallback(a, w, spec, plan_kind, sweeps, path, mode,
                         interpret)
    names = grid_plan.axes
    hs = tuple(grid_plan.halo[d] if names[d] is not None else 0
               for d in range(3))
    m_l, n_l, p_l = grid_plan.local
    m_ext, n_ext, p_ext = m_l + 2 * hs[0], n_l + 2 * hs[1], p_l + 2 * hs[2]
    if block_j is not None and (names[1] is not None
                                or names[2] is not None):
        raise ValueError(f"{spec.name}: block_j tiling is incompatible with "
                         f"a j/k-sharded grid (axes={names}) -- the j/k "
                         f"ghosts are externally materialized; omit block_j")
    if mode == "wavefront" and names[0] is None and per[0]:
        raise ValueError(f"{spec.name}: the wavefront mode cannot run a "
                         f"periodic unsharded i axis inside a process grid "
                         f"(no local pre-extension there); shard i or use "
                         f"mode='fused'")
    acc = acc_dtype_for(a.dtype)
    var = spec.coef == "var"
    wf = spec.canon_weights(w, (m, n, p) if var else None).astype(acc)
    use_overlap = (overlap == "on" and names[0] is not None and hs[0] > 0
                   and m_l >= 2 * hs[0] and block_j is None
                   and mode != "wavefront")
    # overlap tunes for the interior call (resident m_l planes); serialized
    # tunes for the one fully extended slab
    m_tune = m_l if use_overlap else m_ext
    if block_i is not None and m_tune % block_i != 0:
        raise ValueError(
            f"sharded block_i={block_i} must divide the local i extent "
            f"{m_tune} ({'resident, overlap interior' if use_overlap else 'halo-extended'}); "
            f"omit block_i to let the cost model choose")
    bi, bj, rpath = block_i, block_j, path
    run_mode = mode
    if run_mode == "auto":
        if use_overlap:
            run_mode = "fused"      # overlap is a fused-mode executor
        else:
            ebpp = exchange_bytes_per_point(a.dtype.itemsize, hs,
                                            grid_plan.local, sweeps,
                                            spec.n_weights if var else 0)
            sel = autotune_sweeps(m_tune, n_ext, p_ext, a.dtype.itemsize,
                                  sweeps, cplan, block_j=bj, path=path,
                                  external_i_halo=names[0] is not None,
                                  exchange_bytes=ebpp["total"])
            run_mode = "wavefront" if sel.mode == "wavefront" else "fused"
            if run_mode == "wavefront" and names[0] is None and per[0]:
                run_mode = "fused"  # see the explicit-mode raise above
    if run_mode == "wavefront":
        if bj is not None:
            raise ValueError(f"{spec.name}: the wavefront mode is untiled "
                             f"(full-N blocks); omit block_j or use "
                             f"mode='fused'")
        if bi is None:
            bi = wavefront_block_i(m_ext, n_ext, p_ext, a.dtype.itemsize,
                                   sweeps, cplan)
        rpath = "wavefront"
    elif bi is None:
        rpath, bi, bj_auto = autotune_engine(m_tune, n_ext, p_ext,
                                             a.dtype.itemsize, sweeps=sweeps,
                                             plan=cplan, block_j=bj,
                                             path=path)
        bj = bj if bj is not None else bj_auto
        if names[1] is not None or names[2] is not None:
            bj = None               # external j/k ghosts: tiling disallowed
    elif rpath == "auto":
        rpath = "stream"
    if use_overlap:
        bj = None                   # the overlap executor's calls are untiled
    run_overlap = "on" if use_overlap else "off"
    annotate(span, mode=run_mode, path=rpath, block_i=bi, block_j=bj,
             overlap=run_overlap, halo=hs, local=grid_plan.local)
    fn = _grid_sharded_fn(cplan, mesh, names, bi, bj, sweeps, interpret,
                          grid_plan.halo, grid_plan.local,
                          grid_plan.n_shards, (m, n, p), grid_plan.spec,
                          rpath, run_mode, run_overlap)
    return functools.partial(_launch, fn, a, wf)


def stencil_sharded(a: jax.Array, w: jax.Array,
                    stencil: Union[str, int, StencilSpec] = "stencil27",
                    mesh: Optional[Mesh] = None, axis: str = "data",
                    block_i: Optional[int] = None,
                    block_j: Optional[int] = None, plan: str = "auto",
                    sweeps: int = 1, path: str = "auto", mode: str = "fused",
                    bc=None, interpret: Optional[bool] = None,
                    shard_plan: Optional[StencilGridPlan] = None,
                    guard=None, axes=None,
                    overlap: str = "off") -> jax.Array:
    """Halo-exchange execution of ``stencil_apply`` over a process grid.

    ``a`` is ``(..., M, N, P)`` (volumetric specs only); ``mesh`` defaults to
    a 1-D mesh over every visible device.  ``axis`` shards i alone over
    that mesh axis: the 1-D i-chain, which is the process grid
    ``axes=(axis, None, None)``.  Returns the same value as the
    single-device path; falls back to it when the planner declines to shard.
    ``path`` selects the per-shard data-movement strategy exactly as in
    ``stencil_apply`` -- ``"auto"`` streams the halo-extended local slab
    (each local plane fetched once), ``"replicate"`` re-fetches the halo
    neighbours per block (parity escape hatch).  ``mode`` selects the
    per-shard time integration: ``"fused"`` (default) runs one fused
    ``sweeps=s`` kernel per shard; ``"wavefront"`` runs the
    temporal-wavefront pipeline (:func:`~.ops.call_3d_wavefront`) per
    shard; ``"auto"`` races them on the sweeps-aware roofline over the
    halo-extended local slab.  Either way ``s`` sweeps cost *one*
    ``radius * sweep_apps * s``-deep ppermute round -- shard-edge strips
    are redundantly recomputed from the deep halo instead of re-exchanged
    per sweep.  ``bc`` overrides the
    spec's boundary conditions exactly as in ``stencil_apply``; a periodic
    i axis closes the halo exchange into a ring (wrap-around between shard
    0 and shard ``n-1``) while dirichlet/neumann ghosts materialize only on
    the boundary shards via the kernel's global-geometry fill.

    Note: the kernel runs per shard on the halo-extended local slab, so an
    explicit ``block_i`` must divide ``M / n_shards + 2 * sweeps`` (not M);
    it is ignored when the planner falls back to the unsharded path.  Omit
    it to let the plan-aware cost model choose in every configuration
    (including a j-tile width when the local slab overflows VMEM).

    ``axes`` generalizes ``axis`` to an (pi, pj, pk) *process grid*: a
    triple of mesh-axis names (``None`` = that domain axis stays whole),
    e.g. ``axes=("x", "y", "z")`` on a 2x2x2 mesh.  ``shard_plan`` takes
    a :class:`~repro.sharding.StencilGridPlan` from
    :func:`~repro.sharding.stencil_grid_sharding` in place of the plan
    made here; its halo must cover ``radius * sweep_apps * sweeps`` on
    every sharded axis.  Face ghosts are
    exchanged per axis in the order j, k, i on the progressively extended
    slab, so corner/edge ghosts arrive transitively without diagonal
    sends (see :func:`~repro.sharding.stencil_grid_sharding`); per-axis
    BCs pick chain vs ring topology exactly as on the i axis.  Multi-axis
    sharding needs an explicit ``mesh`` and is incompatible with
    ``block_j`` tiling (the j/k ghosts are externally materialized).

    ``overlap="on"`` hides the i-axis exchange behind interior compute:
    the ghost-slab ppermutes are issued first, the interior i-planes
    (which need no ghosts) are swept while the collectives are in flight,
    and the two ``radius * sweep_apps * sweeps``-deep boundary strips are
    then computed from the arrived slabs.  Numerically it computes the
    same rows from the same data -- but through a separate strip kernel,
    so it is not guaranteed bit-exact against ``overlap="off"`` (the
    serialized escape hatch) on non-integer float data; it requires the
    fused mode and quietly serializes when the i axis is unsharded,
    j-tiled, or too thin (``M / n_shards < 2 * radius * sweep_apps *
    sweeps``).
    """
    with TraceAnnotation(SPAN_SHARDED):
        with TraceAnnotation(SPAN_SELECT) as span:
            run = _select_sharded(span, a, w, stencil, mesh, axis, block_i,
                                  block_j, plan, sweeps, path, mode, bc,
                                  interpret, shard_plan, guard, axes,
                                  overlap)
        return run()


def _launch(fn, a: jax.Array, wf: jax.Array) -> jax.Array:
    """Call the jitted shard_map program ``fn`` inside the
    ``stencil.launch`` span."""
    with TraceAnnotation(SPAN_LAUNCH):
        return fn(a, wf)


def _fallback(a, w, spec, plan, sweeps, path, mode, interpret):
    """The single-device entry that runs a call the planner declined to
    shard (it brings its own spans)."""
    if mode == "wavefront":
        from .sweeps import stencil_wavefront
        return functools.partial(stencil_wavefront, a, w, spec,
                                 sweeps=sweeps, plan=plan,
                                 interpret=interpret)
    return functools.partial(stencil_apply, a, w, spec, plan=plan,
                             sweeps=sweeps, path=path, interpret=interpret)


def _select_sharded(span, a, w, stencil, mesh, axis, block_i, block_j, plan,
                    sweeps, path, mode, bc, interpret, shard_plan, guard,
                    axes, overlap) -> Callable[[], jax.Array]:
    """:func:`stencil_sharded`'s host-side choice, made inside the
    ``stencil.select`` ``span``: the call that runs it."""
    if path not in PATH_KINDS:
        raise ValueError(f"unknown path {path!r}; expected one of "
                         f"{PATH_KINDS}")
    if mode not in ("auto", "fused", "wavefront"):
        raise ValueError(f"unknown sharded mode {mode!r}; expected 'auto', "
                         f"'fused', or 'wavefront' (chained per-sweep "
                         f"exchange is exactly what the deep halo removes)")
    if overlap not in ("on", "off"):
        raise ValueError(f"unknown overlap {overlap!r}; expected 'on' or "
                         f"'off'")
    if axes is not None and len(axes) != 3:
        raise ValueError(f"axes must name 3 mesh axes (i, j, k; None = "
                         f"axis stays whole), got {axes!r}")
    spec = get_stencil(stencil)
    policy_src = spec.guard if guard is None else guard
    if policy_src is not None and policy_src != "off":
        from .guard import as_guard, guarded_sharded
        policy = as_guard(policy_src)
        if policy is not None:
            gspec = spec.with_bc(bc) if bc is not None else spec
            return functools.partial(
                guarded_sharded, a, w, gspec, policy, mesh=mesh, axis=axis,
                block_i=block_i, block_j=block_j, plan=plan, sweeps=sweeps,
                path=path, mode=mode, interpret=interpret,
                shard_plan=shard_plan, axes=axes, overlap=overlap)
    if spec.guard != "off":
        spec = spec.with_guard("off")   # guards never reach the trace
    if bc is not None:
        spec = spec.with_bc(bc)
    cplan = compile_plan(spec, plan)
    interpret = resolve_interpret(interpret)
    if mode == "wavefront" and spec.coef == "var":
        raise ValueError(f"{spec.name}: the wavefront mode needs constant "
                         f"coefficients; use mode='fused'")
    if spec.ndim != 3:
        raise ValueError(f"{spec.name}: sharded execution needs a volumetric "
                         f"(ndim=3) spec")
    if a.ndim < 3:
        raise ValueError(f"{spec.name}: need (..., M, N, P), got {a.shape}")
    if mesh is None:
        multi = ((axes is not None
                  and sum(1 for ax in axes if ax is not None) > 1)
                 or (shard_plan is not None
                     and sum(1 for ax in shard_plan.axes
                             if ax is not None) > 1))
        if multi:
            raise ValueError(
                "stencil_sharded: multi-axis sharding needs an explicit "
                "mesh -- build one with jax.make_mesh((pi, pj, pk), names) "
                "and pass its axis names in axes=(ai, aj, ak)")
        name = axis
        if axes is not None and axes[0] is not None:
            name = axes[0]
        elif shard_plan is not None and shard_plan.axes[0] is not None:
            name = shard_plan.axes[0]
        mesh = jax.make_mesh((jax.device_count(),), (name,))
    gaxes = tuple(axes) if axes is not None else (axis, None, None)
    return _select_grid(span, a, w, spec, cplan, mesh, gaxes, shard_plan,
                        block_i, block_j, plan, sweeps, path, mode, overlap,
                        interpret)
