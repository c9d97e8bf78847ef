"""Partition planner: maps parameter/batch/cache pytrees to PartitionSpecs.

Megatron-style tensor parallelism falls out of a largest-divisible-dim
heuristic (column-parallel in-projections, row-parallel out-projections,
vocab-sharded embeddings); expert weights prefer the expert dim (EP,
arctic-480b 128e) and fall back to d_ff TP when the expert count doesn't
divide the axis (mixtral 8e on a 16-way axis).  ``fsdp=True`` additionally
shards a second dim over the data axis (ZeRO-3; with scan-over-layers GSPMD
inserts the per-layer all-gather inside the loop).  Every fallback decision
is recorded as a PlanNote so the dry-run log shows exactly what sharded and
what replicated -- the paper's Table-2 discipline applied to partitioning.
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Any, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

if TYPE_CHECKING:   # annotation-only; a runtime import would close the
    # planner -> models -> kernels -> stencil_engine.sharded -> planner cycle
    from ..models.common import ArchConfig, ShapeConfig


@dataclasses.dataclass
class PlanNote:
    path: str
    shape: Tuple[int, ...]
    spec: Any
    reason: str


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _mesh_axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        return int(np.prod([mesh.shape[n] for n in name]))
    return mesh.shape[name]


def param_sharding(cfg: ArchConfig, params_shapes: Any, mesh: Mesh,
                   fsdp: bool = False
                   ) -> Tuple[Any, List[PlanNote]]:
    """Assign a NamedSharding to every parameter leaf.

    ``params_shapes``: pytree of ShapeDtypeStruct (from jax.eval_shape).
    """
    tp = mesh.shape["model"]
    dp_axis = ("pod", "data") if "pod" in mesh.shape else ("data",)
    dp = _mesh_axis_size(mesh, tuple(dp_axis))
    notes: List[PlanNote] = []
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
    specs = []
    for path, leaf in flat:
        name = _path_str(path)
        shape = tuple(leaf.shape)
        stacked = "layers" in name        # leading scan axis: never shard
        start = 1 if stacked and len(shape) > 1 else 0
        spec: List[Any] = [None] * len(shape)
        reason = "replicated"

        # Megatron-correct dim preference by parameter role: in-projections
        # shard the OUTPUT features (column-parallel), out-projections the
        # INPUT/contraction dim (row-parallel: one activation psum per block
        # instead of per-matmul re-gathers -- EXPERIMENTS.md Perf arctic-H1),
        # embeddings the vocab dim.  Fallback: remaining dims by size.
        from ..flags import flag
        leaf_name = name.rsplit("/", 1)[-1]
        parent = name.split("/")[-2] if "/" in name else ""
        role_row = (flag("megatron_row_parallel")
                    and (parent in ("wo", "out_proj") or leaf_name in ("wo",)))
        role_embed = "embed" in leaf_name or "pos_enc" in leaf_name
        by_size = sorted(range(start, len(shape)), key=lambda i: -shape[i])
        if not flag("megatron_sharding"):
            role_row = role_embed = False
            dims = by_size
        elif role_embed and len(shape) >= 2:
            dims = [start] + [i for i in by_size if i != start]
        elif role_row and len(shape) - start >= 2:
            dims = [len(shape) - 2] + [i for i in by_size
                                       if i != len(shape) - 2]
        elif (len(shape) - start >= 2
              and shape[-1] * 4 >= max(shape[start:])):
            # column-parallel only when the output dim is substantial;
            # sharding a narrow projection head (falcon x_proj: 288 wide)
            # forces per-use re-gathers of everything downstream
            dims = [len(shape) - 1] + [i for i in by_size
                                       if i != len(shape) - 1]
        else:
            dims = by_size
        # Expert weights: prefer expert-parallel over the model axis.
        is_expert = (cfg.n_experts > 0 and len(shape) - start == 3
                     and shape[start] == cfg.n_experts)
        if is_expert and cfg.n_experts % tp == 0:
            spec[start] = "model"
            reason = "expert-parallel (EP)"
        else:
            if is_expert:
                notes.append(PlanNote(
                    name, shape, None,
                    f"EP fallback: {cfg.n_experts} experts not divisible by "
                    f"model={tp}; using d_ff TP"))
            for i in dims:
                if is_expert and i == start:
                    continue
                if shape[i] >= tp and shape[i] % tp == 0:
                    spec[i] = "model"
                    reason = f"TP on dim {i}" + (
                        " (row-parallel)" if role_row and
                        i == len(shape) - 2 else "")
                    break
        if fsdp and len(shape) > 1:
            for i in dims:
                if spec[i] is None and shape[i] >= dp and shape[i] % dp == 0:
                    spec[i] = tuple(dp_axis) if len(dp_axis) > 1 else dp_axis[0]
                    reason += f" + FSDP on dim {i}"
                    break
        notes.append(PlanNote(name, shape, tuple(spec), reason))
        specs.append(NamedSharding(mesh, P(*spec)))
    return jax.tree.unflatten(treedef, specs), notes


def batch_sharding(shape_cfg: ShapeConfig, batch_specs: Dict, mesh: Mesh
                   ) -> Dict[str, NamedSharding]:
    """Batch rows over (pod, data); falls back to sequence sharding (SP)
    when the batch doesn't cover the axis (long-context, batch=1)."""
    dp_axis = ("pod", "data") if "pod" in mesh.shape else ("data",)
    dp = _mesh_axis_size(mesh, tuple(dp_axis))
    out = {}
    b_axis = tuple(dp_axis) if len(dp_axis) > 1 else dp_axis[0]
    for k, v in batch_specs.items():
        if v.shape[0] % dp == 0 and v.shape[0] >= dp:
            out[k] = NamedSharding(mesh, P(b_axis, *([None] * (v.ndim - 1))))
        elif v.ndim > 1 and v.shape[1] % dp == 0:
            out[k] = NamedSharding(mesh, P(None, b_axis,
                                           *([None] * (v.ndim - 2))))
        else:
            out[k] = NamedSharding(mesh, P(*([None] * v.ndim)))
    return out


def decode_state_sharding(cfg: ArchConfig, state_shapes: Any, mesh: Mesh
                          ) -> Any:
    """KV caches / SSM states: batch over data when divisible, else sequence
    (SP for the 500k-context cells); heads or feature dims over model."""
    dp_axis = ("pod", "data") if "pod" in mesh.shape else ("data",)
    dp = _mesh_axis_size(mesh, tuple(dp_axis))
    tp = mesh.shape["model"]
    b_axis = tuple(dp_axis) if len(dp_axis) > 1 else dp_axis[0]
    flat, treedef = jax.tree_util.tree_flatten_with_path(state_shapes)
    specs = []
    for path, leaf in flat:
        shape = tuple(leaf.shape)
        spec: List[Any] = [None] * len(shape)
        # dim 0 is the stacked layer/application axis for caches & states
        used_data = False
        for i in range(1, len(shape)):
            if not used_data and shape[i] >= dp and shape[i] % dp == 0:
                spec[i] = b_axis
                used_data = True
                continue
            if shape[i] >= tp and shape[i] % tp == 0:
                spec[i] = "model"
                break
        specs.append(NamedSharding(mesh, P(*spec)))
    return jax.tree.unflatten(treedef, specs)


@dataclasses.dataclass
class StencilGridPlan:
    """How to split a stencil grid over an (pi, pj, pk) process grid.

    One entry per domain axis (i, j, k): ``axes[d]`` is the mesh axis that
    shards domain axis ``d`` (``None`` = that axis stays whole), and the
    per-axis ``n_shards`` / ``halo`` / ``local`` describe its slab.  An
    axis whose mesh axis has size 1 or whose extent does not divide falls
    back to unsharded with a PlanNote; a shard too thin to cover its own
    halo *raises*.  ``axes=(a, None, None)`` is the 1-D i-chain.
    ``spec`` is the combined ``P(None, ai, aj, ak)`` for a ``(B, M, N, P)``
    operand.  ``periodic[d]`` closes axis ``d``'s exchange into a ring."""
    axes: Tuple[Optional[str], Optional[str], Optional[str]]
    n_shards: Tuple[int, int, int]
    halo: Tuple[int, int, int]
    local: Tuple[int, int, int]
    spec: Any
    notes: List[PlanNote]
    periodic: Tuple[bool, bool, bool] = (False, False, False)

    @property
    def total_shards(self) -> int:
        return int(np.prod(self.n_shards))


def stencil_grid_sharding(shape: Tuple[int, int, int], mesh: Mesh,
                          axes=("data", None, None), sweeps: int = 1,
                          radius=(1, 1, 1),
                          periodic=(False, False, False)) -> StencilGridPlan:
    """Plan multi-axis halo-exchange sharding for an (..., M, N, P) grid.

    ``axes`` names the mesh axis carrying each domain axis (i, j, k) --
    ``None`` leaves that axis whole.  Per sharded axis the shard owns
    ``extent / n`` contiguous planes and exchanges ``radius * sweeps``
    ghost planes per side (callers fold ``sweep_apps`` into ``sweeps``).
    ``axes=(a, None, None)`` plans the 1-D i-chain.  Corner/edge ghosts
    need no diagonal sends: the executor exchanges one axis at a time on
    the progressively extended slab (j, then k, then i), so each later
    exchange carries the earlier axes' ghost columns and the diagonal
    data arrives transitively.  A per-axis ``periodic`` entry closes that
    axis's exchange into a ring.  Indivisible extents and size-1 mesh
    axes fall back (PlanNote'd) to unsharded on that axis; a shard
    thinner than its own halo raises with the shapes in the message."""
    if isinstance(radius, int):
        radius = (radius, radius, radius)
    if len(shape) != 3 or len(axes) != 3:
        raise ValueError(f"stencil_grid_sharding needs a 3-axis shape and "
                         f"axes triple, got shape={shape}, axes={axes}")
    names = ("i", "j", "k")
    out_axes: List[Optional[str]] = []
    n_shards: List[int] = []
    halos: List[int] = []
    local: List[int] = []
    notes: List[PlanNote] = []
    for d in range(3):
        ext, ax = int(shape[d]), axes[d]
        halo = radius[d] * sweeps
        n = _mesh_axis_size(mesh, ax) if ax is not None else 1

        def keep_whole(reason: str) -> None:
            notes.append(PlanNote(f"stencil/{names[d]}-axis", (ext,), None,
                                  reason))
            out_axes.append(None)
            n_shards.append(1)
            halos.append(halo)
            local.append(ext)

        if ax is None:
            out_axes.append(None)
            n_shards.append(1)
            halos.append(halo)
            local.append(ext)
            continue
        if n <= 1:
            keep_whole(f"axis {ax!r} has size {n}; {names[d]} unsharded")
            continue
        if ext % n != 0:
            keep_whole(f"{names[d]}-extent {ext} not divisible by "
                       f"{ax}={n}; replicating along {names[d]}")
            continue
        loc = ext // n
        if loc < halo:
            raise ValueError(
                f"stencil_grid_sharding: {names[d]}-extent {ext} over mesh "
                f"axis {ax!r}={n} leaves {loc} local planes/shard, fewer "
                f"than the {halo}-plane halo (radius {radius[d]} x sweeps "
                f"{sweeps}); need extent // n_shards >= radius * sweeps")
        topo = (f"ring (periodic wrap between shard 0 and shard {n - 1})"
                if periodic[d] else
                "chain (edge shards take boundary ghosts locally)")
        notes.append(PlanNote(
            f"stencil/{names[d]}-axis", (ext,), ax,
            f"{names[d]}-axis split {n} ways x {loc} planes, halo "
            f"{halo}/side (radius {radius[d]} x sweeps {sweeps}), {topo}"))
        out_axes.append(ax)
        n_shards.append(n)
        halos.append(halo)
        local.append(loc)
    part = P(None, *out_axes)
    return StencilGridPlan(tuple(out_axes), tuple(n_shards), tuple(halos),
                           tuple(local), part, notes, tuple(periodic))


def plan_summary(notes: List[PlanNote], max_rows: int = 12) -> str:
    n_rep = sum(1 for n in notes if n.spec is not None
                and all(s is None for s in n.spec))
    n_tp = sum(1 for n in notes if n.spec is not None and "model" in
               [s for s in n.spec if not isinstance(s, tuple)])
    lines = [f"plan: {len(notes)} leaves, {n_tp} model-sharded, "
             f"{n_rep} replicated"]
    for n in notes[:max_rows]:
        lines.append(f"  {n.path:50s} {str(n.shape):28s} -> {n.spec} "
                     f"[{n.reason}]")
    if len(notes) > max_rows:
        lines.append(f"  ... {len(notes) - max_rows} more")
    return "\n".join(lines)
