"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

An alternative mapping for the multi-pod ``pod`` axis (DESIGN.md sect. 6):
layer stages live on successive devices of the pipe axis; activations flow
stage-to-stage via ``lax.ppermute`` while microbatches stream through a
(M + S - 1)-tick schedule.  Bubble fraction is the usual (S-1)/(M+S-1);
each tick overlaps one send with the next compute (XLA schedules the
ppermute against the stage computation).

This module is deliberately model-agnostic: ``stage_fn(stage_params, x)``
is any per-stage function with matching in/out activation shapes (the
transformer trunk satisfies this).  Used standalone + in tests; the
production meshes in this repo default to DP over the pod axis.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Params = Any


def pipeline_forward(stage_fn: Callable, stage_params: Params, x: jax.Array,
                     mesh: Mesh, axis: str = "pipe",
                     n_microbatches: int | None = None) -> jax.Array:
    """Run x through S pipeline stages laid out on ``axis``.

    stage_params: pytree with leading axis S (one slice per stage).
    x: (B, ...) global batch; B must divide into n_microbatches.
    Returns f_{S-1}(...f_0(x)) with identical semantics to the sequential
    composition (verified in tests/test_pipeline.py).
    """
    s = mesh.shape[axis]
    m = n_microbatches or s
    b = x.shape[0]
    if b % m:
        raise ValueError(f"batch {b} must divide into {m} microbatches")
    mb = b // m
    xm = x.reshape(m, mb, *x.shape[1:])

    def local(params_all, xm_loc):
        # params_all arrives as this stage's slice (leading dim 1)
        params_stage = jax.tree.map(lambda t: t[0], params_all)
        idx = jax.lax.axis_index(axis)
        ticks = m + s - 1
        fwd_perm = [(i, i + 1) for i in range(s - 1)]

        def tick(buf, t):
            # stage 0 injects microbatch t; others consume the ppermuted buf
            mb_idx = jnp.clip(t, 0, m - 1)
            inp = jnp.where(idx == 0, xm_loc[mb_idx], buf)
            out = stage_fn(params_stage, inp)
            nxt = jax.lax.ppermute(out, axis, fwd_perm)
            # last stage emits microbatch t - (s - 1) at tick t
            emit_m = t - (s - 1)
            keep = (idx == s - 1) & (emit_m >= 0) & (emit_m < m)
            emitted = jnp.where(keep, out, jnp.zeros_like(out))
            return nxt, emitted

        zero = jnp.zeros_like(xm_loc[0])
        _, emitted = jax.lax.scan(tick, zero, jnp.arange(ticks))
        outs = emitted[s - 1:]                     # (M, mb, ...)
        # broadcast the last stage's results to every pipe rank
        return jax.lax.psum(outs, axis)

    spec_p = jax.tree.map(lambda _: P(axis), stage_params)
    out = jax.shard_map(local, mesh=mesh,
                        in_specs=(spec_p, P()),
                        out_specs=P(),
                        check_vma=False)(stage_params, xm)
    return out.reshape(b, *x.shape[1:])


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
