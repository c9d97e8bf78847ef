"""Time integration: run ``s`` stencil sweeps with temporal wavefront tiling.

A time integration of ``s`` sweeps has three executions with identical
results (bit-exact on integer-valued data -- every mode runs the same
per-application op walk, only the blocking through time differs):

* **chained** -- ``s`` single-sweep :func:`~.ops.stencil_apply` calls: one
  full HBM round-trip per sweep, ``2 * itemsize`` modeled bytes/point each
  (the bit-exact baseline and the only option for shapes no fused window
  fits);
* **fused** -- one call with ``sweeps=s``: ``2 * itemsize / s`` bytes/point,
  but the rotating window and the VPU-redundant strip both deepen with the
  ``radius * s * sweep_apps`` halo, which is what stops large ``s``;
* **wavefront** (this module's tentpole) -- ``s`` pipelined sweep stages
  ride *one* pass over the i-blocks, stage ``t`` consuming planes stage
  ``t-1`` produced one block earlier, so each input plane is fetched from
  HBM once per ``s`` sweeps (``2 * itemsize / s`` bytes/point like fused)
  while every stage carries only the *single-sweep* halo
  ``radius * sweep_apps``.

:func:`stencil_wavefront` is the jitted wavefront entry point;
:func:`stencil_sweep_driver` is the mode dispatcher, racing the three
executions per ``(spec, shape, s)`` on the sweeps-aware roofline
(:func:`~.autotune.autotune_sweeps`) when ``mode="auto"``.

A periodic i axis is handled by caller-side pre-extension: the wavefront
kernel walks i-blocks monotonically and cannot wrap, so the driver
materializes the ``radius * sweep_apps * s`` wrapped rows on each side in
HBM, runs the pipeline with external-halo geometry, and crops -- the same
contract the sharded deep-halo exchange provides via ppermute.
"""

from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .autotune import SWEEP_MODES, autotune_sweeps, wavefront_block_i
from .common import (SPAN_LAUNCH, SPAN_SELECT, SPAN_SWEEP_DRIVER, SPAN_TRACE,
                     annotate)
from .kernel import acc_dtype_for
from .ops import call_3d_wavefront, resolve_interpret, stencil_apply
from .plan import compile_plan
from .spec import StencilSpec, get_stencil


@functools.partial(jax.jit,
                   static_argnames=("stencil", "block_i", "sweeps", "plan",
                                    "bc", "interpret"))
def stencil_wavefront(a: jax.Array, w: jax.Array,
                      stencil: Union[str, int, StencilSpec] = "stencil27",
                      block_i: Optional[int] = None, sweeps: int = 1,
                      plan: str = "auto", bc=None,
                      interpret: Optional[bool] = None) -> jax.Array:
    """``sweeps`` applications through the temporal-wavefront pipeline.

    Bit-exact vs ``sweeps`` chained :func:`~.ops.stencil_apply` calls (and
    the fused ``sweeps=s`` call) on integer-valued data: each pipeline
    stage runs the same compiled plan at single-sweep halo depth, so the
    op walk per application is identical -- only the HBM schedule changes.

    Volumetric constant-coefficient specs only, untiled (full-N) blocks;
    ``block_i`` defaults to the wavefront cost model
    (:func:`~.autotune.wavefront_block_i`) and must divide M (the
    periodic-extended M for a periodic i axis).  ``bc``/``plan``/
    ``interpret`` as in :func:`~.ops.stencil_apply`.
    """
    with TraceAnnotation(SPAN_TRACE) as span:
        if sweeps < 1:
            raise ValueError(f"sweeps must be >= 1, got {sweeps}")
        spec = get_stencil(stencil)
        if spec.guard != "off":
            spec = spec.with_guard("off")   # guards never reach the trace
        if bc is not None:
            spec = spec.with_bc(bc)
        if spec.ndim != 3:
            raise ValueError(f"{spec.name}: the wavefront pipeline is "
                             f"volumetric (ndim=3); use the fused or chained "
                             f"mode for k-only specs")
        cplan = compile_plan(spec, plan)
        acc = acc_dtype_for(a.dtype)
        if a.ndim < 3:
            raise ValueError(f"{spec.name}: need (..., M, N, P), got "
                             f"{a.shape}")
        m, n, p = a.shape[-3:]
        wf = spec.canon_weights(w).astype(acc)
        batch = int(np.prod(a.shape[:-3])) if a.ndim > 3 else 1
        a4 = a.reshape(batch, m, n, p)
        interp = resolve_interpret(interpret)

        # Periodic i: materialize the wrapped deep halo in HBM once per call
        # (the pipeline walks i monotonically), run with external-halo
        # geometry, crop the interior back out.
        h = spec.radius[0] * spec.sweep_apps * sweeps
        periodic_i = spec.bc[0][0].kind == "periodic"
        if periodic_i and h:
            if h > m:
                raise ValueError(
                    f"{spec.name}: periodic wavefront needs the deep halo "
                    f"radius*sweep_apps*sweeps = {h} <= M = {m}; use the "
                    f"fused or chained mode")
            a4 = jnp.concatenate([a4[:, m - h:], a4, a4[:, :h]], axis=1)
            geom = jnp.array([-h, m], jnp.int32)
        else:
            geom = jnp.array([0, m], jnp.int32)
        m_run = a4.shape[1]
        bi = block_i
        if bi is None:
            bi = wavefront_block_i(m_run, n, p, a.dtype.itemsize, sweeps,
                                   cplan)
        annotate(span, path="wavefront", block_i=bi, plan=cplan.kind,
                 sweeps=sweeps)
        out = call_3d_wavefront(a4, wf, geom, cplan, bi, sweeps, interp)
        if periodic_i and h:
            out = out[:, h:h + m]
        return out.reshape(a.shape)


def stencil_sweep_driver(a: jax.Array, w: jax.Array,
                         stencil: Union[str, int, StencilSpec] = "stencil27",
                         sweeps: int = 1, mode: str = "auto",
                         block_i: Optional[int] = None,
                         block_j: Optional[int] = None, plan: str = "auto",
                         path: str = "auto", bc=None,
                         interpret: Optional[bool] = None,
                         guard=None) -> jax.Array:
    """Run ``sweeps`` applications under the modeled-best execution mode.

    ``mode="auto"`` races (fused, wavefront, chained) per
    ``(spec, shape, s)`` via :func:`~.autotune.autotune_sweeps` --
    feasibility first, then fewest modeled HBM bytes/point, then modeled
    time -- and dispatches; ``"fused"``/``"wavefront"``/``"chained"`` pin
    the mode (fused is the bit-exact escape hatch, chained the per-sweep
    round-trip baseline).  All modes agree bit-exactly on integer-valued
    data.  Not itself jitted (the dispatch is static per shape); the
    jitted executors underneath carry the usual caching.

    ``guard`` selects runtime verification + the degradation ladder exactly
    as in :func:`~.ops.stencil_apply`: ``None`` defers to the spec's own
    ``guard`` field, ``"off"`` (the default everywhere) dispatches to the
    historical byte-identical executors, anything else checks the selected
    mode's result and walks the ladder (wavefront -> fused -> chained ->
    stream -> replicate -> oracle) on failure, blacklisting rungs whose
    kernels raise (see :mod:`.guard` and ``last_guard_report()``).
    """
    with TraceAnnotation(SPAN_SWEEP_DRIVER):
        with TraceAnnotation(SPAN_SELECT) as span:
            run = _select_sweeps(span, a, w, stencil, sweeps, mode, block_i,
                                 block_j, plan, path, bc, interpret, guard)
        return run()


def _select_sweeps(span, a, w, stencil, sweeps, mode, block_i, block_j,
                   plan, path, bc, interpret, guard):
    """:func:`stencil_sweep_driver`'s host-side choice, made inside the
    ``stencil.select`` ``span``: the call that runs the chosen mode."""
    if mode not in SWEEP_MODES:
        raise ValueError(f"unknown sweep mode {mode!r}; expected one of "
                         f"{SWEEP_MODES}")
    if sweeps < 1:
        raise ValueError(f"sweeps must be >= 1, got {sweeps}")
    spec = get_stencil(stencil)
    policy_src = spec.guard if guard is None else guard
    if policy_src is not None and policy_src != "off":
        from .guard import as_guard, guarded_driver
        policy = as_guard(policy_src)
        if policy is not None:
            gspec = spec.with_bc(bc) if bc is not None else spec
            return functools.partial(
                guarded_driver, a, w, gspec, policy, sweeps=sweeps,
                mode=mode, block_i=block_i, block_j=block_j, plan=plan,
                path=path, interpret=interpret)
    if bc is not None:
        spec = spec.with_bc(bc)

    def fused():
        return stencil_apply(a, w, spec, block_i=block_i, block_j=block_j,
                             plan=plan, sweeps=sweeps, path=path,
                             interpret=interpret)

    def chained():
        u = a
        for _ in range(sweeps):
            u = stencil_apply(u, w, spec, block_i=block_i, block_j=block_j,
                              plan=plan, sweeps=1, path=path,
                              interpret=interpret)
        return u

    # "auto" races the modes on the sweeps-aware roofline where there is a
    # choice (sweeps > 1, a volumetric spec); else it is the fused call.
    sel, run_mode = None, mode
    if mode == "auto":
        run_mode = "fused"
        if sweeps > 1 and spec.ndim == 3:
            if a.ndim < 3:
                raise ValueError(f"{spec.name}: need (..., M, N, P), got "
                                 f"{a.shape}")
            m, n, p = a.shape[-3:]
            cplan = compile_plan(spec, plan)
            sel = autotune_sweeps(m, n, p, a.dtype.itemsize, sweeps, cplan,
                                  block_j=block_j, path=path)
            run_mode = sel.mode
    if sel is None:
        annotate(span, mode=run_mode)
    else:
        annotate(span, mode=sel.mode, path=sel.path, block_i=sel.block_i,
                 block_j=sel.block_j)
    if run_mode == "chained":
        return chained
    if run_mode == "wavefront":
        bi = sel.block_i if sel is not None and block_i is None else block_i

        def wavefront():
            with TraceAnnotation(SPAN_LAUNCH):
                return stencil_wavefront(a, w, spec, block_i=bi,
                                         sweeps=sweeps, plan=plan,
                                         interpret=interpret)
        return wavefront
    return fused
