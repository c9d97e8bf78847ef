"""Unified Pallas stencil engine: one kernel body, every radius-R stencil.

The paper's central artifact is a synthesis framework that emits many stencil
variants (3/7/27-point, mm/lc register strategies, any jam factor) from one
kernel description.  This package is that idea applied to the repo's Pallas
layer: the former ``stencil3``/``stencil7``/``stencil27`` kernel/ops/ref
triples are now *one* spec registry, compiled to an explicit execution plan
by a pass pipeline (the paper's synthesis step) and run by one kernel body,
at any per-axis radius.

Mask registry
    :func:`get_stencil` / :func:`register_stencil` /
    :func:`list_stencils` / :func:`spec_from_mask`.  Built-ins:
    ``"stencil3"`` (k-only, ``w=(w_edge, w_center)``), ``"stencil7"``
    (``w=(wc, wk, wj, wi)``), ``"stencil27"`` (``w[|di|,|dj|,|dk|]``, shape
    ``(2,2,2)``), and the radius-2 ``"star13"`` (the 4th-order Laplacian
    star, ``w=(wc, w1, w2)``) and ``"box125"`` (5x5x5 box,
    ``w[|di|,|dj|,|dk|]``, shape ``(3,3,3)``).  ``spec_from_mask`` turns
    any odd-shaped coefficient-index mask (``(2r+1)`` per axis) into a
    runnable spec.

Pass-pipeline plan compiler -- :func:`compile_plan` (paper sect. 4)
    A spec compiles to a :class:`StencilPlan` -- a tiny SSA schedule of
    shift/scale/add/fma ops interpreted at trace time by both the kernel
    and the reference.  ``compile_plan`` runs an ordered pass list
    (``build_direct`` -> ``cse`` / ``mirror_factor`` -> ``order_ops``; the
    plan kinds are presets in ``PASS_PRESETS``): ``mirror_factor``
    (per-axis ``|d|``-symmetric specs, any radius) shares k-pair partial
    sums per distance across j then i -- stencil27 drops from 54 shifts +
    53 flop-ops (``direct``, the naive escape hatch) to 8 + 19, the
    radius-2 star13 from 12 + 25 to 12 + 19, box125 from 300 + 249 to
    20 + 63; ``cse`` (arbitrary masks) builds each ``(dj, dk)`` plane shift
    once and reuses it across ``di``; ``order_ops`` re-sequences the
    schedule with the core list scheduler's longest-path-to-sink priority
    and provably never increases peak SSA liveness (:func:`peak_live` --
    the paper's register-pressure constraint as the executor's working
    set).  Shifts are static slices with zero fill on the halo-extended
    block -- no wrap-around values are ever computed then masked.  The
    plan's static op counts drive the cost model.

Execution -- :func:`stencil_apply`
    Batched (arbitrary leading dims) and multi-dtype: bf16/f32 inputs
    accumulate in f32; f64 inputs stay f64 and are bit-identical to
    :func:`stencil_ref` under the same ``plan`` on the reference
    configurations (same op walk, same arithmetic; blocking-invariance is
    exact on integer-valued data -- see :mod:`.plan` on fma contraction).
    ``block_i``/``block_j`` default to the plan- and path-aware roofline
    cost model (:func:`autotune_engine` / :func:`autotune_blocks`), which
    charges the plan's actual ``shifts + flops`` instead of ``2 * taps``
    and the path's real HBM bytes per point.

Plane streaming -- ``stencil_apply(..., path="stream")`` (default via auto)
    The paper's central optimization as the volumetric hot path: the grid
    walks i-blocks in order with a single input operand, and a VMEM
    ``scratch_shapes`` window of ``block_i + radius * sweeps`` planes is
    carried across grid steps (``pl.when``-guarded prime/rotate), so each
    input plane is fetched from HBM exactly once per call and written once
    -- ~2 transfers per point at any radius (:func:`bytes_per_point`), vs
    ``2r + 2`` (untiled) / ``(2r+1)^2 + 1`` (j-tiled) on the
    halo-replicated path, which survives as the ``path="replicate"``
    parity escape hatch (f64 runs of the two paths are bit-identical).

j-tiled blocking -- ``stencil_apply(..., block_j=bj)``
    Blocks become ``(1, bi, bj, P)`` with a j-halo assembled from the 3x3
    neighbour tiles (replicated) or from two sublane-aligned halo blocks
    of the j-neighbours (streaming), so grids whose full N x P slab exceeds
    the VMEM budget -- previously a hard wall -- run at all; the autotuner
    engages it only when no full-N block fits.

Fused sweeps -- ``stencil_apply(..., sweeps=s)``
    Runs ``s`` Jacobi applications inside one ``pallas_call``: blocks are
    widened by ``s`` halo rows (and columns, when j-tiled) from the
    neighbour blocks and only the central rows are written back, cutting
    HBM round-trips from ``s`` to 1 -- the Pallas analogue of the paper's
    register-resident steady-state stream.  Equivalent to ``s`` separate
    applications (requires ``block_i >= sweeps`` and, when j-tiled,
    ``block_j >= sweeps``).

Boundary conditions -- ``spec.with_bc`` / ``stencil_apply(..., bc=...)``
    Per-axis-side :class:`BC`: ``clamp`` (the historical default -- zero
    ghosts + one-point output ring zeroed per sweep), ``periodic`` (wrap;
    paired per axis), ``dirichlet(v)`` (constant ghosts, realized by the
    linearity identity ``stencil(u) = stencil(u - v) + v * sum(w)``), and
    ``neumann`` (zero-flux symmetric mirror).  BC-suffixed builtins
    (``stencil27_periodic``, ...) live in the registry, plans memoize and
    ``describe()`` per variant, the reference is the per-sweep
    ``np.pad``-mode oracle, and every BC runs on both data-movement paths
    at any radius -- the streaming path wraps its lead-in for periodic
    (re-fetching only the first ``radius * sweeps`` planes), the sharded
    path turns the halo exchange into a ring.

Temporal wavefront tiling -- :func:`stencil_sweep_driver` (:mod:`.sweeps`)
    The streaming ideal extended through *time*: ``s`` pipelined sweep
    stages ride one pass over the i-blocks (stage ``t`` consuming planes
    stage ``t-1`` produced one block earlier), so each input plane is
    fetched from HBM once per ``s`` sweeps -- modeled ``2 * itemsize / s``
    bytes/point like the fused call, but every stage carries only the
    *single-sweep* halo ``radius * sweep_apps`` instead of the fused
    ``radius * s * sweep_apps`` window and matching VPU-redundant strip.
    :func:`autotune_sweeps` races (fused, wavefront, chained) per
    ``(spec, shape, s)`` -- feasibility, then fewest modeled bytes/point,
    then modeled time -- and records the verdict in
    ``SweepSelection.describe()["selection"]``; all three modes are
    bit-exact on integer-valued data.  A periodic i axis runs via a
    caller-side HBM pre-extension of the ``radius * sweep_apps * s`` deep
    halo.

Red-black Gauss-Seidel -- ``spec.with_ordering("redblack")``
    Plan-level ordering property: each sweep updates the red checkerboard
    half (global ``(i + j + k)`` parity) in place, then the black half
    reading the fresh red values -- masked in ``run_sweeps`` from the
    kernel's global geometry, mirrored exactly in the NumPy oracle, and
    registered as ``*_redblack`` builtins.  The effective halo per sweep
    doubles (``sweep_apps == 2``), which the cost model, the fused/
    wavefront kernels, and the sharded halo depth all account for.

Sharded execution -- :func:`stencil_sharded`
    ``shard_map`` over a process grid whose 1-D case, ``axis=`` (the grid
    ``(axis, None, None)``), is the i-chain: the partition plan
    (divisibility, halo depth, PlanNotes) comes from
    ``repro.sharding.planner.stencil_grid_sharding``; shards exchange
    ``radius * sweep_apps * sweeps`` halo planes via ``lax.ppermute`` --
    a chain whose edge shards take their boundary ghosts locally, or a
    closed ring when the axis is periodic -- and run the same fused
    kernel (or, with ``mode="wavefront"``, the temporal-wavefront
    pipeline) *once*: ``s`` sweeps cost one exchange round, shard-edge
    strips redundantly recomputed from the deep halo, with
    global-geometry masking keeping shard seams exact.  Compiled
    shard_map programs are memoized keyed on device ids + axis names (not
    ``Mesh`` objects) in a bounded cache.

Multi-axis process grids + overlap -- ``stencil_sharded(axes=..., overlap=...)``
    ``axes=(ai, aj, ak)`` shards the domain over an (pi, pj, pk) process
    grid (plan: ``repro.sharding.planner.stencil_grid_sharding``).  Face
    ghosts are exchanged one axis at a time on the progressively extended
    slab -- j, then k, then i -- so corner/edge ghosts arrive
    *transitively* and no diagonal messages exist
    (:func:`exchange_bytes_per_point` is the per-axis traffic model).
    ``overlap="on"`` hides the i exchange behind compute: the ghost-slab
    ppermutes are issued first, the interior planes (needing no ghosts)
    are swept while the collectives are in flight, and the boundary
    strips are finished from the arrived slabs by a dedicated strip
    kernel; ``overlap="off"`` stays the serialized bit-exact escape
    hatch.  :class:`CorruptHalo` targets any single axis's exchange via
    ``axes=("j",)``-style filters.

Guarded execution -- ``guard=`` on every entry point (:mod:`.guard`)
    Runtime verification + graceful degradation: a :class:`GuardPolicy`
    (or a :data:`GUARD_KINDS` preset string) screens the output for
    NaN/Inf, checks the weight-sum invariant (global under all-periodic
    BCs, per-sampled-plane marginals otherwise/interior residual for
    non-periodic), and optionally spot-checks sampled planes against an
    exact thin-strip oracle (:func:`stencil_ref_planes`); on a detected
    failure or a raised kernel the call retries once, then walks
    ``wavefront -> fused -> chained -> stream -> replicate -> oracle``,
    blacklisting raising candidates in the autotuner and recording every
    demotion in ``last_guard_report().describe()["guard"]``.  The default
    ``guard="off"`` dispatches to the historical byte-identical jitted
    programs.  :mod:`.faults` is the seedable injection harness (bit-flip
    planes, NaN scratch windows, corrupted ppermute halos, raising
    candidates) that proves each detector in ``tests/test_stencil_guard``.

Tier-1 verify: ``PYTHONPATH=src python -m pytest -x -q``
(engine parity lives in ``tests/test_stencil_engine.py``; plan-correctness
property tests in ``tests/test_stencil_plan.py``).
"""

from .autotune import (PATH_KINDS, SWEEP_MODES, SweepSelection,  # noqa: F401
                       autotune_block_i, autotune_blocks, autotune_engine,
                       autotune_sweeps, blacklist_candidate, bytes_per_point,
                       clear_blacklist, exchange_bytes_per_point,
                       is_blacklisted, list_blacklist, pick_block_i,
                       pick_block_rows, wavefront_block_i)
from .compat import (stencil3, stencil3_ref, stencil7, stencil7_ref,  # noqa: F401
                     stencil27, stencil27_ref)
from .common import DEFAULT_VMEM_BUDGET  # noqa: F401
from .faults import (BitFlipPlane, CorruptHalo, FaultInjector,  # noqa: F401
                     NaNScratchWindow, NaNWindow, RaisingCandidate, inject)
from .guard import (LADDER, GuardError, GuardPolicy,  # noqa: F401
                    GuardReport, as_guard, guard_bytes_per_point,
                    last_guard_report, run_guard_checks)
from .kernel import KernelFault  # noqa: F401
from .ops import default_interpret, stencil_apply  # noqa: F401
from .plan import (PASS_PRESETS, PLAN_KINDS, PlanOp,  # noqa: F401
                   StencilPlan, compile_plan, execute_plan,
                   mirror_symmetric, peak_live, run_passes, shift_slice,
                   shift_slice_bc)
from .ref import stencil_ref, stencil_ref_planes  # noqa: F401
from .sharded import stencil_sharded  # noqa: F401
from .spec import (BC, BC_KINDS, CLAMP, GUARD_KINDS, NEUMANN,  # noqa: F401
                   ORDERING_KINDS, PERIODIC, StencilSpec, as_boundary,
                   bc_labels, dirichlet, get_stencil, list_stencils,
                   register_stencil, spec_from_mask)
from .sweeps import stencil_sweep_driver, stencil_wavefront  # noqa: F401
