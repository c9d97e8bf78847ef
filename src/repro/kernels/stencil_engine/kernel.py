"""The Pallas kernel bodies behind every engine stencil.

One compute core serves 3-, 7-, 27-point, the radius-2 star13/box125, and
arbitrary radius-R masks: the spec is first compiled to a
:class:`~.plan.StencilPlan` (the paper's synthesis step, now an explicit
pass pipeline -- a factored partial-sum schedule for symmetric specs, a
CSE'd shift schedule for arbitrary masks, a naive ``direct`` escape hatch,
all re-sequenced by the liveness-ordering pass) and the plan is unrolled at
trace time.  Neighbour access is by static slice + zero pad on the resident
block (:func:`~.plan.shift_slice`), never a wrap-around roll, so no
out-of-domain values are computed then masked.

Two volumetric bodies share that core; all geometry below is per-axis
radius-aware with halo widths ``h = radius * sweeps``:

``stencil3d_kernel`` (the *replicated* path, parity escape hatch)
    The input is passed ``2*ri + 1`` times (untiled) or ``(2*ri + 1) *
    (2*rj + 1)`` times (j-tiled) under block-shifted (clamped) index maps,
    so each grid step re-fetches its halo neighbours from HBM.  Simple,
    stateless, and kept as the ``path="replicate"`` reference.

``stencil3d_stream_kernel`` (the *streaming* path, default)
    The paper's central optimization (sect. 3-4): stream along the i axis
    and keep the active planes resident so each loaded plane is reused by
    every output plane that needs it, instead of being re-fetched.  A single
    input operand walks i-blocks in order on a grid with one extra step; a
    VMEM ``scratch_shapes`` buffer carries a rotating window of ``bi + h``
    input planes (the previous block plus the ``h = ri * sweeps``-deep halo
    tail of the block before it) across grid steps.  Step ``t`` computes
    output block ``t - 1`` from ``[scratch | head h planes of block t]`` and
    then rotates the window -- so every input plane is fetched from HBM
    exactly once per call and written once: ~2 transfers per point, the
    paper's register-resident ideal (VMEM standing in for the register
    file).

Both bodies fuse ``s`` Jacobi sweeps per grid step: the working strip is
``h`` halo planes wider than the output block per side, the sweep loop runs
VMEM-resident via :func:`run_sweeps` (interior mask and zero fill built
once, not per unrolled sweep), and only the central planes are written back
-- one HBM round-trip for ``s`` applications of the operator.

Boundary conditions are a per-axis-side property of the spec
(:class:`~.spec.BC`) and are realized in three places, chosen per axis by
where that axis's ghost cells live (:func:`prepare_strip` wires all of it):

* **halo axes** (i always; j when tiled): the assembled strip's
  out-of-domain positions are *filled* (:func:`fill_ghosts`) -- zeros for
  clamp (pre-sweep only; the ring mask covers later sweeps, and the
  all-clamp default keeps the exact legacy :func:`zero_outside_domain` /
  ring-mask graphs), the constant for dirichlet, a symmetric mirror gather
  for neumann (re-applied after every fused sweep, the kernel form of the
  reference's per-sweep ``np.pad``); a periodic i axis instead *wraps* --
  block index maps reach around the domain and the streaming window gains
  a lead-in step (see ``stencil3d_stream_kernel``), after which the strip
  is contiguous in the periodic metric and needs no refill at all;
* **domain-resident axes** (k always; j untiled): the BC lives in the
  shift primitive's fill (:func:`~.plan.shift_slice_bc`);
* **dirichlet values** ride the linearity identity ``stencil(u) =
  stencil(u - v) + v * sum(w)`` (see :func:`run_sweeps`), since a constant
  fill inside a shift would be wrong for shifted partial sums.

Global geometry (row offset, global M) arrives as a small int32 operand so
the same bodies run unsharded (offset 0) and as the per-shard body of the
halo-exchange ``shard_map`` path -- which is also what makes dirichlet /
neumann ghosts materialize only on the boundary shards.  When ``bj`` is set the grid gains a j dimension: the
replicated body sees the ``(2ri+1) x (2rj+1)`` neighbour tiles; the
streaming body streams i within each j-tile (the tile and two j-halo
blocks of ``hb >= rj * sweeps * sweep_apps`` rows, so planes are fetched
``1 + 2 hb / bj`` times instead of the replicated ``(2ri+1)(2rj+1)`` -- exactly-once
needs the full-N strip in scratch, which is the one regime j-tiling exists
to avoid).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .plan import StencilPlan, execute_plan, shift_slice, shift_slice_bc
from .spec import Boundary


@dataclasses.dataclass(frozen=True)
class KernelFault:
    """A static, hashable in-kernel fault descriptor (fault injection).

    Threaded through the jitted entry points as a static argument (default
    ``None`` -- the traced program is byte-identical to the historical one)
    and realized inside the kernel body, so the fault genuinely lives in
    compiled/interpreted kernel state rather than being patched onto the
    output afterwards.  ``kind="nan_scratch"`` poisons one in-domain plane
    of the stream kernel's rotating VMEM scratch window at prime time
    (``plane`` is taken modulo the block height).  Only :mod:`.faults`
    constructs these."""

    kind: str = "nan_scratch"
    plane: int = 0


def acc_dtype_for(dtype) -> jnp.dtype:
    """bf16/f32 accumulate in f32; the f64 reference path stays f64."""
    return jnp.float64 if dtype == jnp.float64 else jnp.float32


def bc_all_clamp(bc: Boundary) -> bool:
    return all(s.kind == "clamp" for ax in bc for s in ax)


def make_shift(bc: Boundary, j_in_shift: bool,
               k_in_shift: bool = True) -> Callable:
    """The plan executor's shift primitive for this BC configuration.

    Axes whose strip extent *is* the domain extent (k unless its halo is
    externally materialized; j on untiled volumetric blocks / the 1-D path)
    realize their BC inside the shift fill (:func:`~.plan.shift_slice_bc`);
    halo axes keep zero fill -- their BC is realized by :func:`fill_ghosts`
    on the assembled strip.  ``k_in_shift=False`` (a k-sharded slab whose
    ghost planes arrived by exchange) moves k to the fill side too.
    All-clamp configurations keep the exact legacy
    :func:`~.plan.shift_slice` (same traced graph, byte-identical
    programs)."""
    bc_axes = (False, j_in_shift, k_in_shift)
    if all(bc[ax][side].kind in ("clamp", "dirichlet")
           for ax in (1, 2) if bc_axes[ax] for side in (0, 1)):
        return shift_slice          # dirichlet ghosts are zero-fill too
    return lambda t, off: shift_slice_bc(t, off, bc, bc_axes)


def ghost_offset(bc: Boundary) -> float:
    """The shared dirichlet ghost value (0.0 when no side is dirichlet --
    the single-value-per-spec rule is validated at spec construction)."""
    for ax in bc:
        for s in ax:
            if s.kind == "dirichlet":
                return s.value
    return 0.0


def run_sweeps(u: jax.Array, interior: Optional[jax.Array], w: jax.Array,
               plan: StencilPlan, sweeps: int, shift: Callable = shift_slice,
               refill: Optional[Callable] = None,
               parity: Optional[jax.Array] = None) -> jax.Array:
    """Fused Jacobi sweep loop with the loop-invariant clamp-ring select
    hoisted: the interior mask *and* the zero fill it selects against are
    materialized once and reused by every unrolled sweep.  ``interior`` is
    the clamp-side ring mask (``None`` when no side is clamp), ``shift``
    carries the in-shift BCs of the domain-resident axes, and ``refill``
    (when the halo axes carry dirichlet/neumann sides) re-fills the
    out-of-domain ghost strip after every application -- the fused-kernel
    form of the reference's per-sweep ``np.pad``.

    A dirichlet ghost value ``v != 0`` is realized by linearity: the plan
    runs on the offset field ``u - v`` (whose dirichlet ghosts are exactly
    the shifts' zero fill) and ``v * sum(w)`` is added back -- a constant
    fill inside the shifts would be wrong for intermediate partial sums.
    The correction is elementwise: on a variable-coefficient spec ``w[k]``
    is a strip-shaped coefficient plane stack and ``v * sum(w)`` a field.
    A red-black (Gauss-Seidel) spec supplies ``parity`` -- the *global*
    checkerboard ``(i + j + k) % 2 == 0`` of the strip (built once in
    :func:`prepare_strip`) -- and every sweep becomes two masked
    half-applications: the operator is applied and merged at the red
    parity first, then at the black parity reading the red-updated field.
    Information therefore propagates ``2 * radius`` planes per sweep, and
    the halo depth is ``radius * sweeps * spec.sweep_apps``.

    The valid region shrinks ``radius`` planes per application from the
    extended edges, so the central block is exact after ``sweeps``
    applications under the ``h = radius * sweeps * sweep_apps`` halo."""
    zero = None if interior is None else jnp.zeros(u.shape, u.dtype)
    v = ghost_offset(plan.spec.bc)
    off = corr = None
    if v != 0.0:
        off = jnp.asarray(v, u.dtype)
        counts: dict = {}
        for k in plan.spec.w_index:          # static multiplicity per weight
            counts[k] = counts.get(k, 0) + 1
        sumw = sum((w[k] * c for k, c in sorted(counts.items())),
                   jnp.zeros((), u.dtype))
        corr = off * sumw

    def apply_once(x):
        if off is None:
            x = execute_plan(plan, x, w, shift=shift)
        else:
            x = execute_plan(plan, x - off, w, shift=shift) + corr
        if interior is not None:
            x = jnp.where(interior, x, zero)
        return x

    halves = None if parity is None else (parity, ~parity)
    for _ in range(sweeps):
        if halves is None:
            u = apply_once(u)
            if refill is not None:
                u = refill(u)
        else:
            for half in halves:
                u = jnp.where(half, apply_once(u), u)
                if refill is not None:
                    u = refill(u)
    return u


def _volumetric_interior(ext, gi0, j0, m_ref, n_global: int, k0=0,
                         p_top=None):
    """Interior (non-clamp-ring) mask of an extended working strip whose
    row 0 sits at global row ``gi0`` and column 0 at global column ``j0``;
    ``m_ref`` is the (traced) global M.  ``k0``/``p_top`` generalize the
    k axis for k-sharded slabs (default: local k *is* global k).  The
    clamp ring stays one point wide at every radius (out-of-domain reads
    are zeros, matching the reference's zero-fill shifts).  Built once per
    grid step and shared across every fused sweep."""
    if p_top is None:
        p_top = ext[-1]
    gi = gi0 + jax.lax.broadcasted_iota(jnp.int32, ext, 0)
    jj = j0 + jax.lax.broadcasted_iota(jnp.int32, ext, 1)
    kk = k0 + jax.lax.broadcasted_iota(jnp.int32, ext, 2)
    return ((gi > 0) & (gi < m_ref - 1)
            & (jj > 0) & (jj < n_global - 1)
            & (kk > 0) & (kk < p_top - 1))


def _clamp_interior(ext, gi0, j0, m_ref, n_global: int, bc: Boundary,
                    k0=0, p_top=None):
    """Per-side generalization of :func:`_volumetric_interior`: one ring
    constraint per *clamp* side (other BCs apply the operator everywhere and
    realize their ghosts by fill/wrap instead).  ``None`` when no side is
    clamp -- the per-sweep select is skipped entirely."""
    if p_top is None:
        p_top = ext[-1]
    coords = {}

    def coord(axis):
        if axis not in coords:
            base = (gi0, j0, k0)[axis]
            coords[axis] = base + jax.lax.broadcasted_iota(jnp.int32, ext,
                                                           axis)
        return coords[axis]

    tops = (m_ref, n_global, p_top)
    mask = None
    for axis in range(3):
        lo, hi = bc[axis]
        if lo.kind == "clamp":
            t = coord(axis) > 0
            mask = t if mask is None else mask & t
        if hi.kind == "clamp":
            t = coord(axis) < tops[axis] - 1
            mask = t if mask is None else mask & t
    return mask


def _fill_axis(u: jax.Array, axis: int, c0, top, lo, hi,
               include_clamp: bool) -> jax.Array:
    """Fill the out-of-domain positions along one halo axis of the strip:
    ``c0`` is the global coordinate of index 0 and ``top`` the (possibly
    traced) domain extent.  neumann gathers the symmetric mirror of the
    in-domain data (``ghost[-1-q] = u[q]``; the mirror source is always
    resident -- ``block >= radius * sweeps`` is validated); dirichlet is a
    constant select; clamp zeros are applied only pre-sweep
    (``include_clamp`` -- the per-sweep ring mask covers them after every
    application); periodic leaves the strip alone (its halo already holds
    wrapped data)."""
    n_ax = u.shape[axis]
    ii = jax.lax.broadcasted_iota(jnp.int32, (n_ax,), 0)
    g = c0 + ii

    def on_axis(vec):
        return jnp.expand_dims(vec, tuple(a for a in range(u.ndim)
                                          if a != axis))

    if lo.kind == "neumann" or hi.kind == "neumann":
        src = g
        mask = None
        if lo.kind == "neumann":
            src = jnp.where(g < 0, -1 - g, src)
            mask = g < 0
        if hi.kind == "neumann":
            src = jnp.where(g >= top, 2 * top - 1 - g, src)
            m = g >= top
            mask = m if mask is None else mask | m
        local = jnp.clip(src - c0, 0, n_ax - 1)
        u = jnp.where(on_axis(mask), jnp.take(u, local, axis=axis), u)
    for side, oob in ((lo, g < 0), (hi, g >= top)):
        if side.kind == "dirichlet":
            u = jnp.where(on_axis(oob), jnp.asarray(side.value, u.dtype), u)
        elif side.kind == "clamp" and include_clamp:
            u = jnp.where(on_axis(oob), jnp.zeros((), u.dtype), u)
    return u


def fill_ghosts(u: jax.Array, gi0, j0, m_ref, n_global: int, bc: Boundary,
                fill_j: bool, include_clamp: bool, k0=0, p_top=None,
                fill_k: bool = False) -> jax.Array:
    """Realize the halo axes' BCs on an assembled working strip: axis i
    always (its halo is staged/streamed), axis j only when tiled or its
    halo arrived by exchange (untiled single-device strips span the full
    N, so j is an in-shift axis), axis k only when its halo arrived by
    exchange (``fill_k``).  i is filled before j before k, so at ghost
    corners the later axis wins -- the same corner convention as the
    reference's sequential ``np.pad`` (i, then j, then k)."""
    u = _fill_axis(u, u.ndim - 3, gi0, m_ref, *bc[0], include_clamp)
    if fill_j:
        u = _fill_axis(u, u.ndim - 2, j0, n_global, *bc[1], include_clamp)
    if fill_k:
        u = _fill_axis(u, u.ndim - 1, k0, p_top, *bc[2], include_clamp)
    return u


def _needs_refill(bc: Boundary, fill_j: bool, fill_k: bool = False) -> bool:
    axes = (0,) + ((1,) if fill_j else ()) + ((2,) if fill_k else ())
    return any(bc[ax][side].kind in ("dirichlet", "neumann")
               for ax in axes for side in (0, 1))


def _strip_parity(ext, gi0, j0, k0=0) -> jax.Array:
    """Global checkerboard parity ``(i + j + k) % 2 == 0`` ("red") of a
    volumetric working strip whose row 0 sits at global row ``gi0``,
    column 0 at global column ``j0``, and lane 0 at global lane ``k0``
    (0 unless the k axis is sharded -- local k is then global k).  Built
    once per grid step and shared by both half-applications of every
    red-black sweep."""
    gi = gi0 + jax.lax.broadcasted_iota(jnp.int32, ext, 0)
    jj = j0 + jax.lax.broadcasted_iota(jnp.int32, ext, 1)
    kk = k0 + jax.lax.broadcasted_iota(jnp.int32, ext, 2)
    return ((gi + jj + kk) % 2) == 0


def prepare_strip(u: jax.Array, gi0, j0, m_ref, n_global: int,
                  plan: StencilPlan, tiled_j: bool, k0=0, p_top=None,
                  fill_k: bool = False):
    """Shared BC set-up for the volumetric kernel bodies: fill the assembled
    strip's out-of-domain ghosts, and return the per-sweep machinery
    ``(u, interior, shift, refill, parity)`` for :func:`run_sweeps`
    (``parity`` is the global red checkerboard for red-black specs, else
    ``None``).  ``k0``/``p_top``/``fill_k`` describe a k axis whose ghost
    planes were materialized externally (the k-sharded exchange): k then
    leaves the shift primitive and its BC is realized by fill at *global*
    k coordinates, exactly like a tiled j.  All-clamp specs take the exact
    legacy path (zero fill at radius >= 2 only, the ring mask, plain
    zero-fill shifts) so default-BC programs stay byte-identical."""
    bc = plan.spec.bc
    parity = (_strip_parity(u.shape, gi0, j0, k0)
              if plan.spec.ordering == "redblack" else None)
    if bc_all_clamp(bc):
        u = zero_outside_domain(u, gi0, j0, m_ref, n_global,
                                plan.spec.radius, k0, p_top, fill_k)
        return (u, _volumetric_interior(u.shape, gi0, j0, m_ref, n_global,
                                        k0, p_top),
                shift_slice, None, parity)
    u = fill_ghosts(u, gi0, j0, m_ref, n_global, bc, fill_j=tiled_j,
                    include_clamp=True, k0=k0, p_top=p_top, fill_k=fill_k)
    interior = _clamp_interior(u.shape, gi0, j0, m_ref, n_global, bc,
                               k0, p_top)
    shift = make_shift(bc, j_in_shift=not tiled_j, k_in_shift=not fill_k)
    refill = None
    if _needs_refill(bc, fill_j=tiled_j, fill_k=fill_k):
        def refill(v):
            return fill_ghosts(v, gi0, j0, m_ref, n_global, bc,
                               fill_j=tiled_j, include_clamp=False,
                               k0=k0, p_top=p_top, fill_k=fill_k)
    return u, interior, shift, refill, parity


def zero_outside_domain(u: jax.Array, gi0, j0, m_ref, n_global: int,
                        radius: Tuple[int, int, int], k0=0, p_top=None,
                        zero_k: bool = False) -> jax.Array:
    """Zero strip positions outside the global (M, N[, P]) domain.

    Clamped neighbour index maps duplicate edge blocks, so strip rows/
    columns beyond the domain hold copies of in-domain data instead of the
    zeros the reference's zero-fill shifts assume.  At radius 1 those
    positions only ever feed rows the Dirichlet mask zeroes (proved by the
    one-plane-per-sweep shrink argument), so this is skipped to keep the
    radius-1 programs byte-identical; at radius >= 2 an interior point at
    distance 1 from the boundary genuinely reads distance-2 neighbours
    across it, so the zeros must be materialized.  ``zero_k`` extends the
    check to a k axis with externally materialized ghosts (a chain-edge
    exchange already delivers genuine zeros there, so this is defensive)."""
    if (radius[0] <= 1 and radius[1] <= 1
            and (not zero_k or radius[2] <= 1)):
        return u
    gi = gi0 + jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
    jj = j0 + jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    ok = (gi >= 0) & (gi < m_ref) & (jj >= 0) & (jj < n_global)
    if zero_k:
        kk = k0 + jax.lax.broadcasted_iota(jnp.int32, u.shape, 2)
        ok = ok & (kk >= 0) & (kk < p_top)
    return jnp.where(ok, u, jnp.zeros(u.shape, u.dtype))


def _concat_halo(prev, cur, nxt, h: int, axis: int) -> jax.Array:
    """``[tail h of prev | cur | head h of nxt]`` along ``axis`` -- the halo
    slices are taken *before* concatenating so the temporary stays at
    ``block + 2h``, never the full staged neighbourhood.  ``h`` never
    exceeds the block extent (``block >= radius * sweeps`` is validated),
    so the +-1 neighbours always cover the halo; any outer views remain
    staged-but-unread (the replicated path's honest ``2r + 1`` cost)."""
    if h == 0:
        return cur
    src = [slice(None)] * cur.ndim
    src[axis] = slice(-h, None)
    head = [slice(None)] * cur.ndim
    head[axis] = slice(0, h)
    return jnp.concatenate([prev[tuple(src)], cur, nxt[tuple(head)]],
                           axis=axis)


def _assemble_strip(tiles, ri: int, rj: int, hi: int, hj: int,
                    bj: Optional[int], ax: int) -> jax.Array:
    """Build the halo-extended working strip from staged neighbour tiles.

    ``tiles`` is the flat ``2ri + 1`` (untiled) or row-major ``(2ri + 1) x
    (2rj + 1)`` (j-tiled) view list with block axes already stripped; ``ax``
    is the position of the i axis within each tile (0 for the field, 1 for
    a coefficient stack with its leading weight axis)."""
    if bj is None:
        prev, cur, nxt = (tiles[ri + d] if hi else tiles[ri]
                          for d in (-1, 0, 1))
        return _concat_halo(prev, cur, nxt, hi, ax)
    nj = 2 * rj + 1

    def jrow(ii: int) -> jax.Array:
        row = [tiles[ii * nj + rj + (d if hj else 0)] for d in (-1, 0, 1)]
        return _concat_halo(*row, hj, ax + 1)

    mid = jrow(ri)
    rows = (jrow(ri - 1), mid, jrow(ri + 1)) if hi else (mid, mid, mid)
    return _concat_halo(*rows, hi, ax)


def stencil3d_kernel(*refs, plan: StencilPlan, bi: int, bj: Optional[int],
                     n_global: int, sweeps: int, acc_dtype,
                     ext_j: bool = False, ext_k: bool = False,
                     p_global: Optional[int] = None):
    """Replicated-halo fused-sweep volumetric kernel (``path="replicate"``).

    ``refs`` is ``(*blocks, geom_ref, w_ref, o_ref)`` where ``blocks`` holds
    the ``2ri + 1`` i-neighbour views (untiled, blocks ``(1, bi, N, P)``) or
    the ``(2ri + 1) x (2rj + 1)`` i/j-neighbour views in row-major
    ``(di, dj)`` order (j-tiled, blocks ``(1, bi, bj, P)``).  ``geom_ref`` =
    (global row of this array's row 0, global M) -- both 0 and the local M
    for the single-device path; shard-dependent under shard_map.  A
    multi-axis-sharded slab extends ``geom_ref`` with the global j/k
    coordinates of its column/lane 0 (``ext_j``/``ext_k`` mark those axes'
    ghosts as externally materialized; ``p_global`` is then the global P).

    Variable-coefficient specs replace the single resident ``w_ref`` with a
    full parallel set of coefficient views (``refs`` becomes ``(*blocks,
    geom_ref, *wblocks, o_ref)``, blocks ``(n_weights, bi, ., P)`` under the
    same index maps), and the coefficient strip is assembled exactly like the
    field strip -- coefficients are evaluated at the *output* point, so every
    in-domain strip position sees its true coefficients; out-of-domain
    positions only feed outputs the ghost fill / interior mask overwrites.
    """
    var = plan.spec.coef == "var"
    o_ref = refs[-1]
    if var:
        nv = (len(refs) - 2) // 2
        blocks, geom_ref, wblocks = refs[:nv], refs[nv], refs[nv + 1:-1]
    else:
        geom_ref, w_ref = refs[-3], refs[-2]
        blocks = refs[:-3]
    ri, rj, _ = plan.spec.radius
    i_blk = pl.program_id(1)
    s = sweeps
    apps = plan.spec.sweep_apps
    hi = ri * s * apps
    hj = rj * s * apps
    if bj is None:
        j0 = geom_ref[2] if ext_j else 0
    else:
        j_blk = pl.program_id(2)
        j0 = j_blk * bj - hj
    k0 = geom_ref[3] if ext_k else 0
    u = _assemble_strip([blk[0] for blk in blocks], ri, rj, hi, hj, bj,
                        0).astype(acc_dtype)
    if var:
        w = _assemble_strip([wb[...] for wb in wblocks], ri, rj, hi, hj,
                            bj, 1)
    else:
        w = w_ref[...]
    gi0 = geom_ref[0] + i_blk * bi - hi
    u, interior, shift, refill, parity = prepare_strip(
        u, gi0, j0, geom_ref[1], n_global, plan, bj is not None or ext_j,
        k0=k0, p_top=p_global if ext_k else None, fill_k=ext_k)
    u = run_sweeps(u, interior, w, plan, s, shift=shift, refill=refill,
                   parity=parity)
    out = u[hi:hi + bi] if bj is None else u[hi:hi + bi, hj:hj + bj]
    o_ref[0] = out.astype(o_ref.dtype)


def stencil3d_stream_kernel(*refs, plan: StencilPlan, bi: int,
                            bj: Optional[int], n_global: int, sweeps: int,
                            acc_dtype, wrap_i: bool = False,
                            fault: Optional[KernelFault] = None,
                            ext_j: bool = False, ext_k: bool = False,
                            p_global: Optional[int] = None,
                            i_halo: bool = False):
    """Plane-streaming fused-sweep volumetric kernel (``path="stream"``).

    ``refs`` is ``(*views, geom_ref, w_ref, o_ref, scr_ref)``.  Untiled
    (``bj is None``): ``views`` is one identity-mapped block ``(1, bi, N,
    P)`` and the grid's trailing dim runs ``nbi + 1`` steps; j-tiled:
    ``views`` are the left j-halo block ``(1, bi, hb, P)``, the tile ``(1,
    bi, bj, P)`` and the right j-halo block (``hb`` rows from
    :func:`~.ops.halo_block_rows`, at least the ``hj``-deep halo; the tile
    alone when ``hj == 0``), and the grid is ``(B, nbj, nbi + 1)`` with i
    innermost, so the stream restarts per j-tile.  ``scr_ref`` is VMEM
    scratch of ``bi + h`` input planes (``h = ri * sweeps``) carried
    across grid steps: planes ``[0, h)`` are the tail of the block before
    the previous one (zeros above the domain), planes ``[h, h + bi)`` are
    the previous block.

    Step 0 primes the window; step ``t >= 1`` assembles the working strip
    ``[scratch | head h planes of block t]`` (at ``t == nbi`` the clamped
    index map re-presents block ``nbi - 1``, whose planes land only at
    ``gi >= M`` where the domain zeroing / interior mask kills them -- and
    an unchanged block index costs no DMA under Pallas revisiting
    semantics), runs the fused sweeps, writes output block ``t - 1`` via
    the lagged output index map, and rotates the window.  Net HBM traffic:
    each input plane read once, each output plane written once.

    ``i_halo=True`` (a shard whose i ghosts arrived by exchange): the two
    exchanged ``h``-plane slabs follow the weights as operands, ``(*lo,
    *hi)`` in the same views as the field (blocks ``(1, h, ., P)``, fetched
    once per j-tile).  Step 0 primes the ghost planes from ``lo`` in place
    of zeros, and the last step takes its head planes from ``hi`` in place
    of the re-presented block's, so the stream runs over the resident
    planes alone and ``geom_ref[0]`` is the global row of the first of
    them.

    ``wrap_i=True`` (the i axis is periodic, realized here rather than by a
    pre-exchanged shard halo): the stream gains one more lead-in step and
    walks the *wrapped* block sequence ``nbi-1, 0, 1, ..., nbi-1, 0``
    (``i_src(t) = (t + nbi - 1) % nbi``).  Step 0 stages only the tail
    ``h`` planes of the last block (the ghost rows below global row 0),
    step 1 stages block 0, and step ``t >= 2`` computes output block
    ``t - 2``; the final step re-fetches block 0's head planes for the tail
    of the sweep -- the periodic case's only extra HBM traffic (~2 extra
    block reads per call).

    Variable-coefficient specs co-stream the coefficient planes: ``refs``
    becomes ``(*views, geom_ref, *wviews, o_ref, scr_ref, wscr_ref)`` with
    the coefficient views ``(n_weights, bi, ., P)`` walking the same block
    sequence as the field views, and ``wscr_ref`` a second VMEM rotating
    window ``(n_weights, bi + h, ., P)`` primed and rotated in lockstep with
    ``scr_ref`` -- so coefficient planes, like field planes, are fetched
    from HBM exactly once per call.  Coefficients are evaluated at the
    *output* point; the above-domain lead-in planes are zero-primed and
    only ever feed discarded ghost outputs.  With ``i_halo`` the exchanged
    coefficient slabs follow the field's, ``(*lo, *hi, *wlo, *whi)``.
    """
    var = plan.spec.coef == "var"
    ri, rj, _ = plan.spec.radius
    s = sweeps
    apps = plan.spec.sweep_apps
    hi = ri * s * apps
    hj = rj * s * apps
    nv = 3 if bj is not None and hj else 1     # field views per operand
    views, geom_ref = refs[:nv], refs[nv]
    k = nv + 1
    if var:
        wviews, k = refs[k:k + nv], k + nv
    else:
        w_ref, k = refs[k], k + 1
    n_slabs = (4 if var else 2) if i_halo else 0
    slabs = [refs[k + q * nv:k + (q + 1) * nv]    # lo, hi (, wlo, whi)
             for q in range(n_slabs)]
    k += n_slabs * nv
    o_ref, scr_ref = refs[k], refs[k + 1]
    if var:
        wscr_ref = refs[k + 2]
    lag = 2 if wrap_i else 1
    k0 = geom_ref[3] if ext_k else 0

    def assemble(vs, field: bool):
        """The block of views ``vs`` (field: batch axis dropped), with its
        j-halo columns on a j-tiled call."""
        def load(v):
            return v[0] if field else v[...]
        if bj is None:
            return load(vs[0])
        row = vs if hj else vs * 3          # (left halo, tile, right halo)
        return _concat_halo(*(load(v) for v in row), hj, 1 if field else 2)

    if bj is None:
        t = pl.program_id(1)
    else:
        t = pl.program_id(2)
        j_blk = pl.program_id(1)
    cur = assemble(views, True)                     # (bi, ., P)
    if var:
        wcur = assemble(wviews, False)              # (nw, bi, ., P)
    if bj is None:
        j0 = geom_ref[2] if ext_j else 0
    else:
        j0 = j_blk * bj - hj

    if wrap_i:
        @pl.when(t == 0)
        def _prime_ghost():
            # cur is the *last* block: its tail h planes are the wrapped
            # ghost rows below global row 0.
            scr_ref[:hi] = cur[bi - hi:bi]
            if var:
                wscr_ref[:, :hi] = wcur[:, bi - hi:bi]

        @pl.when(t == 1)
        def _prime_first():
            scr_ref[hi:] = cur                             # block 0
            if var:
                wscr_ref[:, hi:] = wcur
    else:
        @pl.when(t == 0)
        def _prime():
            # Window for output block 0: block "-1" is the exchanged low
            # slab, or above the domain (zeros; the strip fill / interior
            # mask handles them), block 0 = cur.
            if hi and i_halo:
                scr_ref[:hi] = assemble(slabs[0], True)
                if var:
                    wscr_ref[:, :hi] = assemble(slabs[2], False)
            elif hi:
                scr_ref[:hi] = jnp.zeros((hi,) + cur.shape[1:], cur.dtype)
                if var:
                    wscr_ref[:, :hi] = jnp.zeros(
                        wcur.shape[:1] + (hi,) + wcur.shape[2:], wcur.dtype)
            scr_ref[hi:] = cur
            if var:
                wscr_ref[:, hi:] = wcur

    if (fault is not None and fault.kind == "nan_scratch"
            and jnp.issubdtype(jnp.dtype(scr_ref.dtype), jnp.inexact)):
        # Fault injection (tests): poison one in-domain scratch plane right
        # after priming, so the NaN rides the rotating window into the
        # first computed output block.
        @pl.when(t == lag - 1)
        def _inject_fault():
            fp = hi + (fault.plane % bi)
            scr_ref[fp] = jnp.full(scr_ref.shape[1:], jnp.nan, scr_ref.dtype)

    def head(block, slab: int, field: bool):
        """The ``hi`` planes after the scratch window: the head of
        ``block``, and on the last step of an ``i_halo`` stream (whose
        re-presented block lies before them) the high exchanged slab,
        ``slabs[slab]``."""
        planes = block[:hi] if field else block[:, :hi]
        if not i_halo:
            return planes
        last = t == pl.num_programs(1 if bj is None else 2) - 1
        return jnp.where(last, assemble(slabs[slab], field), planes)

    @pl.when(t >= lag)
    def _compute():
        u = (jnp.concatenate([scr_ref[...], head(cur, 1, True)],
                             axis=0) if hi
             else scr_ref[...]).astype(acc_dtype)          # (bi + 2hi, ., P)
        if var:
            w = (jnp.concatenate([wscr_ref[...],
                                  head(wcur, 3, False)], axis=1)
                 if hi else wscr_ref[...])                 # (nw, bi + 2hi, ., P)
        else:
            w = w_ref[...]
        gi0 = geom_ref[0] + (t - lag) * bi - hi
        u, interior, shift, refill, parity = prepare_strip(
            u, gi0, j0, geom_ref[1], n_global, plan, bj is not None or ext_j,
            k0=k0, p_top=p_global if ext_k else None, fill_k=ext_k)
        u = run_sweeps(u, interior, w, plan, s, shift=shift, refill=refill,
                       parity=parity)
        out = u[hi:hi + bi] if bj is None else u[hi:hi + bi, hj:hj + bj]
        o_ref[0] = out.astype(o_ref.dtype)
        # Rotate the window: new tail = last hi planes of the block the
        # scratch currently holds.
        if hi:
            tail = scr_ref[bi:bi + hi]
            scr_ref[:hi] = tail
            if var:
                wtail = wscr_ref[:, bi:bi + hi]
                wscr_ref[:, :hi] = wtail
        scr_ref[hi:] = cur
        if var:
            wscr_ref[:, hi:] = wcur


def stencil3d_wavefront_kernel(*refs, plan: StencilPlan, bi: int,
                               n_global: int, sweeps: int, acc_dtype,
                               ext_j: bool = False, ext_k: bool = False,
                               p_global: Optional[int] = None):
    """Temporal wavefront-tiled volumetric kernel: ``s = sweeps`` *pipelined*
    sweep stages ride one pass over the i-blocks, each input plane fetched
    from HBM once per ``s`` sweeps (vs once per sweep chained, and vs a
    ``radius * s``-deep fused halo).

    ``refs`` is ``(view, geom_ref, w_ref, o_ref, scr_in, *stage_scrs)``:
    one identity-mapped input block ``(1, bi, N, P)`` on a grid of
    ``nbi + s`` steps, plus ``s`` rotating VMEM windows of ``bi + ha``
    planes each (``ha = radius * sweep_apps``, the *single-sweep* halo --
    the wavefront's VMEM advantage over the fused path's ``radius * s``).
    ``scr_in`` holds input-dtype planes for stage 1; ``stage_scrs[q-2]``
    holds stage ``q-1``'s accumulation-dtype output planes for stage ``q``.

    The pipeline is *skewed*: at step ``t``, stage ``q`` computes its block
    ``t - q`` from ``[window | head ha planes of stage q-1's block
    t - q + 1]`` -- stage ``q`` consumes planes stage ``q - 1`` produced
    exactly one step (= ``bi`` >= ``ha`` planes) earlier, so every stage
    runs the full single-sweep BC machinery (:func:`prepare_strip` +
    :func:`run_sweeps`) at its own global geometry and the final stage's
    central block is exact.  Blocks with out-of-domain indices (the ``s``
    pipeline fill/drain steps) only ever produce planes at out-of-domain
    global rows, which the ghost fill / clamp masking of the *consuming*
    stage overwrites -- the same shrink argument as the fused halo, applied
    per stage.  The lagged output map writes stage ``s``'s block ``t - s``;
    steps ``t < s`` write pipeline-fill garbage that is overwritten at
    ``t = s`` before the block index advances (Pallas revisiting
    semantics, the same trick as the streaming kernel's lead-in).

    A periodic i axis is handled by the *caller* (HBM pre-extension with
    ``radius * sweep_apps * s`` wrapped rows and external-halo geometry --
    see :func:`~.sweeps.stencil_wavefront`), so this body never wraps;
    variable-coefficient specs take the fused/chained paths instead (their
    coefficient planes would need an ``s``-block-deep window here).
    """
    view, geom_ref, w_ref, o_ref, scr_in = refs[:5]
    stage_scrs = refs[5:]
    ri, _, _ = plan.spec.radius
    ha = ri * plan.spec.sweep_apps
    s = sweeps
    t = pl.program_id(1)
    cur = view[0]                                          # (bi, N, P)

    @pl.when(t == 0)
    def _prime():
        # Stage 1's window for block 0: block "-1" is above the domain
        # (zeros; strip fill / interior mask of every stage handles them).
        if ha:
            scr_in[:ha] = jnp.zeros((ha,) + cur.shape[1:], cur.dtype)
        scr_in[ha:] = cur

    @pl.when(t >= 1)
    def _compute():
        w = w_ref[...]
        j0 = geom_ref[2] if ext_j else 0
        k0 = geom_ref[3] if ext_k else 0

        def stage(win_ref, nxt, blk):
            u = (jnp.concatenate([win_ref[...], nxt[:ha]], axis=0) if ha
                 else win_ref[...]).astype(acc_dtype)      # (bi + 2ha, N, P)
            gi0 = geom_ref[0] + blk * bi - ha
            u, interior, shift, refill, parity = prepare_strip(
                u, gi0, j0, geom_ref[1], n_global, plan, ext_j, k0=k0,
                p_top=p_global if ext_k else None, fill_k=ext_k)
            u = run_sweeps(u, interior, w, plan, 1, shift=shift,
                           refill=refill, parity=parity)
            return u[ha:ha + bi]

        nxt = cur            # stage q's "next block" = stage q-1's block t-q+1
        for q in range(1, s + 1):
            win = scr_in if q == 1 else stage_scrs[q - 2]
            val = stage(win, nxt, t - q)
            # rotate window q-1 forward with its freshly arrived block
            if ha:
                tail = win[bi:bi + ha]
                win[:ha] = tail
            win[ha:] = nxt
            nxt = val
        o_ref[0] = nxt.astype(o_ref.dtype)


def stencil3d_strip_kernel(*refs, plan: StencilPlan, h: int, n_global: int,
                           sweeps: int, acc_dtype, ext_j: bool = False,
                           ext_k: bool = False,
                           p_global: Optional[int] = None):
    """Boundary-strip fused-sweep kernel: one fully pre-extended i-strip.

    The compute/communication-overlap executor splits a shard's sweep into
    an interior pass (no i ghosts needed, runs while the i-axis ppermutes
    are in flight) and two thin boundary strips computed from the arrived
    ghost slabs.  This body is the strip entry: ``refs`` is ``(u_ref,
    geom_ref, w_ref, o_ref)`` with a single identity-mapped block ``(1,
    rows, N, P)`` whose ``rows = out_rows + 2h`` i-planes *already include*
    the ``h`` exchanged ghost planes per side (``h = radius * sweeps *
    sweep_apps``), so no staging, streaming window, or neighbour views are
    involved -- the strip runs :func:`prepare_strip` + :func:`run_sweeps`
    at its global geometry and writes the central ``rows - 2h`` planes.
    (The replicated path cannot serve here: at a single i-block its clamped
    index maps would duplicate resident data into halo positions that are
    genuinely interior on a sharded slab.)  Variable-coefficient specs pass
    the matching pre-extended coefficient strip as ``w_ref``."""
    u_ref, geom_ref, w_ref, o_ref = refs
    u = u_ref[0].astype(acc_dtype)
    w = w_ref[...]          # var: the whole (n_weights, rows, N, P) strip
    gi0 = geom_ref[0]
    j0 = geom_ref[2] if ext_j else 0
    k0 = geom_ref[3] if ext_k else 0
    u, interior, shift, refill, parity = prepare_strip(
        u, gi0, j0, geom_ref[1], n_global, plan, ext_j, k0=k0,
        p_top=p_global if ext_k else None, fill_k=ext_k)
    u = run_sweeps(u, interior, w, plan, sweeps, shift=shift, refill=refill,
                   parity=parity)
    rows = u.shape[0]
    o_ref[0] = u[h:rows - h].astype(o_ref.dtype)


def stencil1d_kernel(a_ref, w_ref, o_ref, *, plan: StencilPlan, sweeps: int,
                     acc_dtype):
    """k-only kernel over ``(block_rows, P)`` blocks; rows are independent,
    so fused sweeps need no halo at all (the k axis is fully resident and
    its BC -- wrap / constant / mirror / zero fill -- lives in the shift
    primitive)."""
    u = a_ref[...].astype(acc_dtype)
    w = w_ref[...]
    p = u.shape[-1]
    klo, khi = plan.spec.bc[2]
    interior = None
    if klo.kind == "clamp" or khi.kind == "clamp":
        kk = jax.lax.broadcasted_iota(jnp.int32, u.shape, u.ndim - 1)
        interior = ((kk > 0) & (kk < p - 1) if klo.kind == khi.kind
                    else (kk > 0) if klo.kind == "clamp" else (kk < p - 1))
    parity = None
    if plan.spec.ordering == "redblack":
        kk = jax.lax.broadcasted_iota(jnp.int32, u.shape, u.ndim - 1)
        parity = (kk % 2) == 0       # rows are independent: parity is k-only
    shift = make_shift(plan.spec.bc, j_in_shift=False)
    o_ref[...] = run_sweeps(u, interior, w, plan, sweeps, shift=shift,
                            parity=parity).astype(o_ref.dtype)
