"""Plan-, path-, and radius-aware cost-model block selection.

Same shape of reasoning as ``repro.core.perfmodel``: performance is
``min(compute limit, bandwidth limit)``, so the modeled time of one grid step
is ``max(DMA time, VPU time)`` and we pick the feasible (path, block) pair
minimizing the modeled time per output point:

* DMA bytes/step: every staged input view plus one output block.  The
  *replicated* path stages ``2*ri + 1`` i-neighbour views untiled
  (``(2*ri + 1) * (2*rj + 1)`` i/j views j-tiled); the *streaming* path
  fetches each i-block once (one identity-mapped view untiled, the
  ``2*rj + 1`` j-neighbour views j-tiled) and carries the halo in VMEM
  scratch -- see :func:`bytes_per_point`.  Streaming therefore stays at
  ~2 transfers/point *at any radius* while the replicated cost grows with
  ``r``; fused sweeps amortize the traffic over ``s`` applications.
* VPU ops/step: the *plan's* static op counts -- ``flops + shifts`` per
  point of the extended working strip per sweep (a lane shift occupies the
  VPU like a flop), not a blind ``2 * taps``.  A factored stencil27 plan
  (8 shifts + 19 flops) therefore models ~4x cheaper than the naive
  schedule (54 + 53), which shifts the DMA/VPU crossover -- the paper's
  Table-4 point that the synthesized schedule changes which resource binds.
* VMEM residency: what Mosaic allocates for one grid step must fit the
  budget -- every staged input view and the output block twice (Pallas
  double-buffers them), the streaming path's ``bi + ri * sweeps``-plane
  rotating scratch window, and ``peak_live + 2`` working strips in the
  accumulation dtype (the assembled input strip, the plan's live partial
  sums, the masked result).  The budget is half the scoped-VMEM limit the
  kernels compile with (:data:`~.common.VMEM_LIMIT_BYTES`): the paper's
  Table-2 "registers required vs registers available" constraint in VMEM
  terms.

Feasible blocks divide M (and N when j-tiled -- Pallas grid constraint) and
satisfy ``bi >= ri * s`` / ``bj >= rj * s`` (the carried window / +-1-block
halo must cover the fused-sweep halo depth); a j-tile is a whole number of
sublane tiles (:func:`sublane_rows`) wherever N allows one.  j-tiling
engages only when no full-N block fits the budget.  Ties prefer sublane
multiples (8), as the old heuristic did.

:func:`autotune_engine` is the top-level entry: it races the streaming and
replicated rooflines per shape and returns ``(path, block_i, block_j)`` --
streaming wins whenever it is feasible (it moves 2 bytes/point where the
replicated path moves ``2*ri + 2``, or ``2*rj + 2`` vs
``(2ri+1)(2rj+1) + 1`` j-tiled) but the replicated path remains reachable
as the ``path="replicate"`` parity escape hatch and for shapes where the
streaming scratch window itself overflows VMEM.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Union

from .common import DEFAULT_VMEM_BUDGET, divisors as _divisors

# TPU-v5e-flavoured roofline constants (per core), only ever used as a ratio.
HBM_BW = 819e9          # bytes/s
VPU_FLOPS = 3e12        # f32 elementwise flop/s

PATH_KINDS = ("auto", "stream", "replicate")

# Time-integration execution modes for ``s`` sweeps (see autotune_sweeps):
# one fused pallas_call with a radius*s-deep halo, s pipelined wavefront
# stages each carrying the single-sweep halo, or s chained single-sweep
# calls (one HBM round-trip per sweep -- the bit-exact baseline).
SWEEP_MODES = ("auto", "fused", "wavefront", "chained")

RadiusLike = Union[int, Tuple[int, int, int], None]

# ---------------------------------------------------------------------------
# Guarded-execution candidate blacklist.
#
# Historically a candidate that raised at compile or run time was fatal: the
# autotuner would happily re-select it on the next call and the caller would
# crash again.  The guard's degradation ladder (see .guard) records a
# demoted candidate here after its retry also fails, and the two autotune
# races consult the registry so a known-bad (spec, mode) / (spec, path)
# pair drops out of future selections -- the process-local analogue of the
# paper's "discard variants the simulator rejects" step.  Empty by default,
# so unguarded behaviour is unchanged.
# ---------------------------------------------------------------------------

_BLACKLIST: set = set()


def blacklist_candidate(spec_name: str, mode: Optional[str] = None,
                        path: Optional[str] = None) -> None:
    """Exclude a sweep ``mode`` and/or a data-movement ``path`` from future
    ``auto`` races for the named spec (pinned modes/paths stay reachable --
    an explicit request is the caller's escape hatch)."""
    if mode is None and path is None:
        raise ValueError("blacklist_candidate needs a mode and/or a path")
    if mode is not None:
        _BLACKLIST.add((str(spec_name), "mode", mode))
    if path is not None:
        _BLACKLIST.add((str(spec_name), "path", path))


def is_blacklisted(spec_name: str, mode: Optional[str] = None,
                   path: Optional[str] = None) -> bool:
    return ((mode is not None
             and (str(spec_name), "mode", mode) in _BLACKLIST)
            or (path is not None
                and (str(spec_name), "path", path) in _BLACKLIST))


def clear_blacklist(spec_name: Optional[str] = None) -> None:
    """Drop every blacklist entry (or only the named spec's)."""
    if spec_name is None:
        _BLACKLIST.clear()
    else:
        for e in [e for e in _BLACKLIST if e[0] == str(spec_name)]:
            _BLACKLIST.discard(e)


def list_blacklist() -> Tuple[Tuple[str, str, str], ...]:
    return tuple(sorted(_BLACKLIST))


def _radius3(radius: RadiusLike, plan=None) -> Tuple[int, int, int]:
    """Canonicalize a radius argument: ``None`` defers to the plan's spec
    (radius-1 when neither is given); an int is isotropic."""
    if radius is None:
        if plan is not None:
            return tuple(plan.spec.radius)
        return (1, 1, 1)
    if isinstance(radius, int):
        return (radius, radius, radius)
    r = tuple(int(x) for x in radius)
    if len(r) != 3:
        raise ValueError(f"radius must be an int or 3-tuple, got {radius!r}")
    return r


def _plan_ops(plan, taps: int) -> Tuple[int, int]:
    """(shifts, flops) per extended point per sweep; ``plan=None`` keeps the
    legacy ``2 * taps`` pure-flop accounting for old callers."""
    if plan is not None:
        return plan.shifts, plan.flops
    return 0, 2 * taps


def _plan_apps(plan) -> int:
    """Operator applications per sweep: ``spec.sweep_apps`` (2 for red-black
    Gauss-Seidel, whose fused halo and VPU work both double), 1 without a
    plan (legacy Jacobi callers)."""
    if plan is not None:
        return plan.spec.sweep_apps
    return 1


# Peak live values of the factored stencil27 schedule: the residency
# model's stand-in when a legacy caller passes no plan.
LEGACY_PEAK_LIVE = 6


def _plan_live(plan) -> int:
    """Simultaneously live SSA values of the plan (:func:`~.plan.peak_live`);
    :data:`LEGACY_PEAK_LIVE` without one."""
    if plan is not None:
        return plan.peak_live
    return LEGACY_PEAK_LIVE


def sublane_rows(itemsize: int) -> int:
    """Rows of one TPU sublane tile (8 x 32-bit; packed dtypes pack more):
    the granule a j-tile's second-to-last block dim must be a multiple of."""
    return 8 * max(1, 4 // itemsize)


def _plan_var_weights(plan) -> int:
    """Coefficient planes staged per input view: ``n_weights`` for a
    variable-coefficient plan (its weights are domain-shaped fields that
    ride the same block walk as the input), 0 for constant coefficients
    (register/VMEM-resident, no per-block traffic)."""
    if plan is not None and plan.spec.coef == "var":
        return plan.spec.n_weights
    return 0


def _views(j_tiled: bool, path: str, ri: int = 1, rj: int = 1) -> int:
    """Input views staged per grid step: the streaming path fetches each
    block once (plus the ``2rj + 1`` j-neighbour tiles when j-tiled); the
    replicated path re-fetches the full ``2ri + 1`` (untiled) /
    ``(2ri+1)(2rj+1)`` (j-tiled) halo neighbourhood."""
    if path == "stream":
        return (2 * rj + 1) if j_tiled else 1
    return (2 * ri + 1) * (2 * rj + 1) if j_tiled else (2 * ri + 1)


def _geometry(bi: int, bj: Optional[int], n: int, sweeps: int,
              path: str = "replicate",
              radius: Tuple[int, int, int] = (1, 1, 1), apps: int = 1):
    """(output columns, extended columns, staged input views) per step."""
    ri, rj, _ = radius
    if bj is None:
        return n, n, _views(False, path, ri, rj)
    return bj, bj + 2 * rj * sweeps * apps, _views(True, path, ri, rj)


def bytes_per_point(path: str, itemsize: int, j_tiled: bool = False,
                    sweeps: int = 1, radius: RadiusLike = None,
                    coef: str = "const", n_weights: int = 0) -> float:
    """Modeled HBM bytes moved per output point per call (reads + the one
    write), amortized over ``sweeps`` fused applications.

    Streaming untiled is the paper's ideal ~2 transfers/point *at any
    radius*: each input plane read exactly once, each output plane written
    once.  The replicated path re-reads every plane per staged view:
    ``2ri + 2`` untiled, ``(2ri+1)(2rj+1) + 1`` j-tiled (4 and 10 at
    radius 1, 6 and 26 at radius 2).  Streaming j-tiled re-reads along j
    only (``2rj + 2``).

    ``coef="var"`` adds the coefficient traffic: ``n_weights`` planes ride
    every staged input view (co-streamed / replicated exactly like the
    field), so e.g. streaming untiled moves ``2 + n_weights`` transfers
    per point.  Constant coefficients stay resident and move nothing.

    ``path="wavefront"`` is the temporal-wavefront pipeline (untiled,
    constant coefficients): one read + one write amortized over ``sweeps``
    pipelined stages -- ``2 * itemsize / sweeps``, the paper's streaming
    ideal extended through time.  (A periodic i axis re-reads its
    ``2 * radius * sweep_apps * sweeps`` pre-extension rows on top of
    this canonical figure; see ``autotune_sweeps`` for the shape-aware
    number.)
    """
    if path == "wavefront":
        if j_tiled:
            raise ValueError("the wavefront path is untiled (full-N blocks)")
        if coef == "var":
            raise ValueError("the wavefront path needs constant coefficients")
        return 2 * itemsize / sweeps
    if path not in ("stream", "replicate"):
        raise ValueError(f"unknown path {path!r}; expected 'stream', "
                         f"'replicate', or 'wavefront'")
    ri, rj, _ = _radius3(radius)
    nv = _views(j_tiled, path, ri, rj)
    wv = nv * n_weights if coef == "var" else 0
    return (nv + wv + 1) * itemsize / sweeps


def _step_time(bi: int, bj: Optional[int], n: int, p: int, itemsize: int,
               sweeps: int, shifts: int, flops: int,
               path: str = "replicate",
               radius: Tuple[int, int, int] = (1, 1, 1),
               var_weights: int = 0, apps: int = 1) -> float:
    """``var_weights`` > 0 (a variable-coefficient plan) charges that many
    coefficient planes of DMA per staged input view -- modeled at the input
    itemsize (the coefficient dtype is the accumulation dtype; the model is
    only consumed relatively, per spec).  ``apps`` scales the VPU work and
    the halo-redundant strip extent (red-black runs 2 masked applications
    per sweep)."""
    wj, ej, views = _geometry(bi, bj, n, sweeps, path, radius, apps)
    dma = ((views * (1 + var_weights) + 1.0) * bi * wj * p * itemsize
           / HBM_BW)
    vpu = ((flops + shifts) * apps * sweeps
           * (bi + 2 * radius[0] * sweeps * apps) * ej * p / VPU_FLOPS)
    return max(dma, vpu) / (bi * wj * p * sweeps)  # per output point-sweep


def _fits(bi: int, bj: Optional[int], n: int, p: int, itemsize: int,
          sweeps: int, acc_itemsize: int, vmem_budget: int,
          path: str = "replicate",
          radius: Tuple[int, int, int] = (1, 1, 1),
          var_weights: int = 0, apps: int = 1,
          live: int = LEGACY_PEAK_LIVE) -> bool:
    """Does one grid step's VMEM residency fit ``vmem_budget``?  Counts
    what Mosaic allocates: the staged input views and the output block,
    each double-buffered; the streaming scratch window; and ``live + 2``
    working strips (``live`` = the plan's peak live values) in the
    accumulation dtype."""
    wj, ej, views = _geometry(bi, bj, n, sweeps, path, radius, apps)
    hi = radius[0] * sweeps * apps
    io_tiles = 2 * (views + 1) * bi * wj * p * itemsize
    scratch = (bi + hi) * ej * p * itemsize if path == "stream" else 0
    strip = (bi + 2 * hi) * ej * p * acc_itemsize
    working = (live + 2) * strip
    if var_weights:
        # staged coefficient views (double-buffered) + co-rotating scratch +
        # assembled strip, all in the accumulation dtype
        io_tiles += 2 * views * var_weights * bi * wj * p * acc_itemsize
        if path == "stream":
            scratch += var_weights * (bi + hi) * ej * p * acc_itemsize
        working += var_weights * strip
    return io_tiles + scratch + working <= vmem_budget


def autotune_blocks(m: int, n: int, p: int, itemsize: int,
                    sweeps: int = 1, plan=None, taps: int = 27,
                    acc_itemsize: int = 4,
                    vmem_budget: int = DEFAULT_VMEM_BUDGET,
                    block_j: Optional[int] = None,
                    allow_j_tiling: bool = True,
                    path: str = "replicate",
                    radius: RadiusLike = None
                    ) -> Tuple[int, Optional[int]]:
    """Smallest modeled time per output point over feasible blockings of one
    execution ``path``.

    Returns ``(block_i, block_j)`` with ``block_j=None`` meaning untiled
    (full-N) blocks.  j-tiling is considered only when no untiled block fits
    ``vmem_budget`` (or when ``block_j`` pins a tile width).  ``plan`` (a
    :class:`~.plan.StencilPlan`) supplies the actual shift/flop counts and
    the spec radius; without it the legacy radius-1 ``2 * taps`` estimate
    applies.
    """
    shifts, flops = _plan_ops(plan, taps)
    var_w = _plan_var_weights(plan)
    apps = _plan_apps(plan)
    live = _plan_live(plan)
    rad = _radius3(radius, plan)
    min_bi = max(1, rad[0] * sweeps * apps)
    min_bj = max(1, rad[1] * sweeps * apps)
    cands_i = [bi for bi in _divisors(m) if bi >= min_bi] or [m]

    def key(bi: int, bj: Optional[int]):
        return (_step_time(bi, bj, n, p, itemsize, sweeps, shifts, flops,
                           path, rad, var_w, apps),
                0 if (bi % 8 == 0 or bi < 8) else 1,
                -bi * (bj if bj is not None else n))

    if block_j is None:
        feasible = [bi for bi in cands_i
                    if _fits(bi, None, n, p, itemsize, sweeps, acc_itemsize,
                             vmem_budget, path, rad, var_w, apps, live)]
        if feasible:
            return min(feasible, key=lambda bi: key(bi, None)), None
        if not allow_j_tiling:      # nothing fits: smallest legal block
            return cands_i[0], None
        cands_j = [bj for bj in _divisors(n) if min_bj <= bj < n] or [n]
        granule = sublane_rows(itemsize)
        cands_j = [bj for bj in cands_j if bj % granule == 0] or cands_j
    else:
        cands_j = [block_j]
    pairs = [(bi, bj) for bi in cands_i for bj in cands_j
             if _fits(bi, bj, n, p, itemsize, sweeps, acc_itemsize,
                      vmem_budget, path, rad, var_w, apps, live)]
    if pairs:
        return min(pairs, key=lambda bb: key(*bb))
    return cands_i[0], cands_j[0]   # nothing fits: smallest legal tile


def autotune_engine(m: int, n: int, p: int, itemsize: int,
                    sweeps: int = 1, plan=None, taps: int = 27,
                    acc_itemsize: int = 4,
                    vmem_budget: int = DEFAULT_VMEM_BUDGET,
                    block_j: Optional[int] = None,
                    path: str = "auto",
                    radius: RadiusLike = None
                    ) -> Tuple[str, int, Optional[int]]:
    """Race the streaming and replicated rooflines: returns the modeled-best
    ``(path, block_i, block_j)`` over both paths' feasible blockings.

    ``path="stream"``/``"replicate"`` pins the path and only tunes blocks.
    Feasible streaming (strictly fewer HBM bytes per point at any radius,
    same VPU work) wins every tie; the replicated path is chosen only when
    the streaming scratch window cannot fit the VMEM budget at any legal
    blocking.
    """
    if path not in PATH_KINDS:
        raise ValueError(f"unknown path {path!r}; expected one of "
                         f"{PATH_KINDS}")
    shifts, flops = _plan_ops(plan, taps)
    var_w = _plan_var_weights(plan)
    apps = _plan_apps(plan)
    rad = _radius3(radius, plan)
    cands = ("stream", "replicate") if path == "auto" else (path,)
    if path == "auto" and plan is not None:
        live = tuple(c for c in cands
                     if not is_blacklisted(plan.spec.name, path=c))
        cands = live or cands       # never race an empty field
    best = None
    for cand in cands:
        bi, bj = autotune_blocks(m, n, p, itemsize, sweeps=sweeps, plan=plan,
                                 taps=taps, acc_itemsize=acc_itemsize,
                                 vmem_budget=vmem_budget, block_j=block_j,
                                 path=cand, radius=rad)
        feasible = _fits(bi, bj, n, p, itemsize, sweeps, acc_itemsize,
                         vmem_budget, cand, rad, var_w, apps,
                         _plan_live(plan))
        t = _step_time(bi, bj, n, p, itemsize, sweeps, shifts, flops, cand,
                       rad, var_w, apps)
        # infeasible blockings only ever win when nothing fits anywhere;
        # the streaming path wins exact ties (strictly fewer HBM bytes).
        rank = (0 if feasible else 1, t, 0 if cand == "stream" else 1)
        if best is None or rank < best[0]:
            best = (rank, cand, bi, bj)
    return best[1], best[2], best[3]


def autotune_block_i(m: int, n: int, p: int, itemsize: int,
                     sweeps: int = 1, taps: int = 27, plan=None,
                     acc_itemsize: int = 4,
                     vmem_budget: int = DEFAULT_VMEM_BUDGET) -> int:
    """Untiled (full-N) i-block choice -- the pre-j-tiling entry point."""
    bi, _ = autotune_blocks(m, n, p, itemsize, sweeps=sweeps, plan=plan,
                            taps=taps, acc_itemsize=acc_itemsize,
                            vmem_budget=vmem_budget, allow_j_tiling=False)
    return bi


def pick_block_i(m: int, n: int, p: int, itemsize: int,
                 vmem_budget: int = DEFAULT_VMEM_BUDGET) -> int:
    """Legacy entry point (kept for the MXU kernel and old callers)."""
    return autotune_block_i(m, n, p, itemsize, sweeps=1, taps=27,
                            vmem_budget=vmem_budget)


def pick_block_rows(rows: int, p: int, itemsize: int,
                    vmem_budget: int = DEFAULT_VMEM_BUDGET) -> int:
    """Row-block choice for the k-only (1-D) path: the largest power-of-two
    row count whose tile fits the budget; when no power of two divides
    ``rows``, the largest *fitting divisor* (never an over-budget full-rows
    block, which the old fallback could return)."""
    for cand in (256, 128, 64, 32, 16, 8):
        if rows % cand == 0 and cand * p * itemsize <= vmem_budget:
            return cand
    for cand in sorted(_divisors(rows), reverse=True):
        if cand * p * itemsize <= vmem_budget:
            return cand
    return 1


# ---------------------------------------------------------------------------
# Temporal wavefront tiling: the sweeps-aware roofline race.
# ---------------------------------------------------------------------------

def _fits_wavefront(bi: int, n: int, p: int, itemsize: int, sweeps: int,
                    acc_itemsize: int, vmem_budget: int, ha: int,
                    live: int = LEGACY_PEAK_LIVE) -> bool:
    """VMEM residency of the wavefront pipeline at block ``bi``: the staged
    input view + output block (each double-buffered), ``sweeps`` rotating
    stage windows of ``bi + ha`` planes (stage 1 input dtype, the rest
    accumulation dtype), and the ``live + 2`` working strips of one stage
    compute (stages run sequentially within a step, so one stage's strips
    bound the live set)."""
    io = 2 * 2 * bi * n * p * itemsize
    scratch = ((bi + ha) * n * p * itemsize
               + (sweeps - 1) * (bi + ha) * n * p * acc_itemsize)
    working = (live + 2) * (bi + 2 * ha) * n * p * acc_itemsize
    return io + scratch + working <= vmem_budget


def _wavefront_step_time(bi: int, n: int, p: int, itemsize: int, sweeps: int,
                         shifts: int, flops: int, ha: int, apps: int,
                         read_factor: float = 1.0) -> float:
    """Modeled seconds per output point-sweep of the wavefront pipeline:
    one input-block read (scaled by ``read_factor`` -- ``m_ext / m`` for a
    periodic pre-extension, 1 otherwise) + one output-block write per step
    against ``sweeps`` stage computations, each over the ``bi + 2 * ha``
    single-sweep strip (the wavefront's VPU advantage: the fused path's
    strip is ``bi + 2 * radius * sweeps * apps`` wide)."""
    dma = (read_factor + 1.0) * bi * n * p * itemsize / HBM_BW
    vpu = ((flops + shifts) * apps * sweeps * (bi + 2 * ha) * n * p
           / VPU_FLOPS)
    return max(dma, vpu) / (bi * n * p * sweeps)


def wavefront_block_i(m: int, n: int, p: int, itemsize: int, sweeps: int,
                      plan, acc_itemsize: int = 4,
                      vmem_budget: int = DEFAULT_VMEM_BUDGET) -> int:
    """Best modeled i-block for the wavefront pipeline over divisors of
    ``m`` (the *run* extent -- pre-extended for periodic) with
    ``bi >= ha``; the smallest legal block when nothing fits the budget
    (mirroring :func:`autotune_blocks`)."""
    shifts, flops = _plan_ops(plan, plan.spec.taps)
    apps = _plan_apps(plan)
    ha = plan.spec.radius[0] * apps
    cands = [bi for bi in _divisors(m) if bi >= ha] or [m]

    def key(bi: int):
        return (_wavefront_step_time(bi, n, p, itemsize, sweeps, shifts,
                                     flops, ha, apps),
                0 if (bi % 8 == 0 or bi < 8) else 1, -bi)

    feasible = [bi for bi in cands
                if _fits_wavefront(bi, n, p, itemsize, sweeps, acc_itemsize,
                                   vmem_budget, ha, plan.peak_live)]
    if feasible:
        return min(feasible, key=key)
    return cands[0]


def exchange_bytes_per_point(itemsize: int, halos, locs, sweeps: int = 1,
                             n_weights: int = 0) -> Dict[str, float]:
    """Per-axis halo-exchange traffic of the multi-axis sharded executor,
    in bytes per owned point per sweep.

    ``halos``/``locs`` are the per-domain-axis (i, j, k) deep halo and
    local extent (halo 0 = axis unsharded, no exchange).  The executor
    exchanges one axis at a time on the *progressively extended* slab
    (j, then k, then i -- the transitive corner fill), so each later
    axis's face slabs carry the earlier axes' ghost columns and grow
    accordingly: that growth is the entire cost of corner correctness --
    no extra diagonal messages.  Each sharded axis moves two face slabs
    per shard (send+receive symmetric, counted once as arriving bytes);
    variable-coefficient specs ship ``n_weights`` coefficient slabs with
    the field (the ``1 + n_weights`` factor).  ``sweeps`` fused sweeps
    amortize the one deep exchange, exactly like the compute-side deep
    halo.  Returns ``{"i", "j", "k", "total"}``."""
    hi, hj, hk = halos
    m_l, n_l, p_l = locs
    stacks = itemsize * (1 + n_weights)
    bj = 2 * hj * m_l * p_l * stacks
    bk = 2 * hk * m_l * (n_l + 2 * hj) * stacks
    bi = 2 * hi * (n_l + 2 * hj) * (p_l + 2 * hk) * stacks
    pts = m_l * n_l * p_l * max(sweeps, 1)
    return {"i": bi / pts, "j": bj / pts, "k": bk / pts,
            "total": (bi + bj + bk) / pts}


@dataclasses.dataclass(frozen=True)
class SweepSelection:
    """The sweeps-aware autotuner's verdict for one ``(spec, shape, s)``.

    ``mode`` is the chosen time-integration strategy (fused / wavefront /
    chained), ``path`` the spatial data-movement path underneath it
    (``"wavefront"`` for the wavefront pipeline; stream/replicate
    otherwise), and ``candidates`` the full race table --
    ``(mode, path, block_i, block_j, bytes_per_point, time_per_point,
    feasible)`` per entrant -- which is what lets the regression gate
    judge whether a selection flip is consistent with the fresh model.
    """

    sweeps: int
    mode: str
    path: str
    block_i: int
    block_j: Optional[int]
    bytes_per_point: float          # modeled HBM bytes per point per sweep
    time_per_point: float           # modeled seconds per point per sweep
    candidates: Tuple[Tuple[str, str, int, Optional[int], float, float,
                            bool], ...] = ()

    def describe(self) -> Dict[str, object]:
        """Machine-readable selection record (benchmark / JSON form)."""
        return {"selection": {
            "sweeps": self.sweeps, "mode": self.mode, "path": self.path,
            "block_i": self.block_i, "block_j": self.block_j,
            "bytes_per_point": self.bytes_per_point,
            "time_per_point": self.time_per_point,
            "candidates": [
                {"mode": mo, "path": pa, "block_i": bi, "block_j": bj,
                 "bytes_per_point": bpp, "time_per_point": tpp,
                 "feasible": fe}
                for mo, pa, bi, bj, bpp, tpp, fe in self.candidates],
        }}


def autotune_sweeps(m: int, n: int, p: int, itemsize: int, sweeps: int,
                    plan, acc_itemsize: int = 4,
                    vmem_budget: int = DEFAULT_VMEM_BUDGET,
                    block_j: Optional[int] = None, mode: str = "auto",
                    path: str = "auto", external_i_halo: bool = False,
                    exchange_bytes: float = 0.0) -> SweepSelection:
    """Race the three ways to run ``sweeps`` applications -- one *fused*
    call (halo ``radius * sweeps * apps``), the *wavefront* pipeline (each
    plane fetched once per ``sweeps``, per-stage halo ``radius * apps``),
    and ``sweeps`` *chained* single-sweep calls -- on a sweeps-aware
    roofline, per ``(spec, shape, s)``.

    Ranking follows the paper's accounting: feasible entrants first, then
    *fewest modeled HBM bytes/point* (these kernels are memory-bound by
    thesis -- traffic is the resource being optimized), with modeled time
    per point-sweep breaking byte ties.  The fused stream and the
    wavefront both model ``2 * itemsize / sweeps`` vs ``2 * itemsize``
    chained, so the byte tie between them is broken by VPU redundancy
    (the fused strip is ``2 * radius * sweeps * apps`` wider than the
    output block, the wavefront strip only ``2 * radius * apps``) and,
    before that, by VMEM residency (the deep fused halo is exactly what
    stops large ``s``).  Exact ties break toward the wavefront at
    ``sweeps > 1`` and toward the fused call at ``sweeps == 1`` (they
    are the same program there; fused is the bit-exact escape hatch).  The wavefront entrant is infeasible for
    variable coefficients, j-tiled shapes, and 1-D specs; a periodic i
    axis (unless ``external_i_halo``) charges its pre-extension re-read
    (``m + 2 * radius * apps * sweeps`` rows read per ``m`` written).

    ``exchange_bytes`` (the sharded caller: per-point-per-sweep halo
    traffic from :func:`exchange_bytes_per_point`) is added to every
    entrant's modeled bytes/point -- the deep exchange happens once per
    call whatever the mode, so it shifts the reported totals without
    re-ranking the race.
    """
    if mode not in SWEEP_MODES:
        raise ValueError(f"unknown sweep mode {mode!r}; expected one of "
                         f"{SWEEP_MODES}")
    if sweeps < 1:
        raise ValueError(f"sweeps must be >= 1, got {sweeps}")
    spec = plan.spec
    shifts, flops = _plan_ops(plan, spec.taps)
    var_w = _plan_var_weights(plan)
    apps = _plan_apps(plan)
    rad = _radius3(None, plan)
    modes = ("fused", "wavefront", "chained") if mode == "auto" else (mode,)
    if mode == "auto":
        live = tuple(c for c in modes
                     if not is_blacklisted(spec.name, mode=c))
        modes = live or modes       # never race an empty field
    pref = ({"wavefront": 0, "fused": 1, "chained": 2} if sweeps > 1
            else {"fused": 0, "wavefront": 1, "chained": 2})
    rows = []
    for cand in modes:
        if cand == "wavefront":
            ha = rad[0] * apps
            per_i = spec.bc[0][0].kind == "periodic" and not external_i_halo
            h = ha * sweeps
            m_wf = m + 2 * h if (per_i and h) else m
            kind_ok = (spec.ndim == 3 and spec.coef == "const"
                       and block_j is None and not (per_i and h > m))
            if not kind_ok:
                if mode != "auto":
                    raise ValueError(
                        f"{spec.name}: wavefront mode needs a volumetric "
                        f"constant-coefficient spec, untiled j, and (for "
                        f"periodic i) halo {h} <= M={m}")
                continue
            bi = wavefront_block_i(m_wf, n, p, itemsize, sweeps, plan,
                                   acc_itemsize, vmem_budget)
            feasible = _fits_wavefront(bi, n, p, itemsize, sweeps,
                                       acc_itemsize, vmem_budget, ha,
                                       plan.peak_live)
            read_f = m_wf / m
            bpp = (read_f + 1.0) * itemsize / sweeps
            tpp = _wavefront_step_time(bi, n, p, itemsize, sweeps, shifts,
                                       flops, ha, apps, read_f)
            rows.append((cand, "wavefront", bi, None, bpp + exchange_bytes,
                         tpp, feasible))
        else:
            s_eff = sweeps if cand == "fused" else 1
            rpath, bi, bj = autotune_engine(
                m, n, p, itemsize, sweeps=s_eff, plan=plan,
                acc_itemsize=acc_itemsize, vmem_budget=vmem_budget,
                block_j=block_j, path=path)
            feasible = _fits(bi, bj, n, p, itemsize, s_eff, acc_itemsize,
                             vmem_budget, rpath, rad, var_w, apps,
                             plan.peak_live)
            bpp = bytes_per_point(rpath, itemsize, bj is not None, s_eff,
                                  rad, spec.coef, spec.n_weights)
            tpp = _step_time(bi, bj, n, p, itemsize, s_eff, shifts, flops,
                             rpath, rad, var_w, apps)
            rows.append((cand, rpath, bi, bj, bpp + exchange_bytes, tpp,
                         feasible))
    if not rows:
        raise ValueError(f"{spec.name}: no feasible sweep mode candidates")
    best = min(rows, key=lambda r: (not r[6], r[4], r[5], pref[r[0]]))
    return SweepSelection(sweeps=sweeps, mode=best[0], path=best[1],
                          block_i=best[2], block_j=best[3],
                          bytes_per_point=best[4], time_per_point=best[5],
                          candidates=tuple(rows))
