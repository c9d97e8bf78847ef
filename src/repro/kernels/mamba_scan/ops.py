"""Jitted public entry point for the Mamba selective-scan Pallas kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel import mamba_scan_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
               c: jax.Array, d: jax.Array, chunk: int = 64,
               interpret: bool = True) -> jax.Array:
    """Selective scan: x, dt (B, L, D); a (D, N); bm, c (B, L, N); d (D,)."""
    bsz, seq, dim = x.shape
    n = a.shape[1]
    chunk = min(chunk, seq)
    if seq % chunk != 0:
        raise ValueError(f"chunk {chunk} must divide L={seq}")
    nchunk = seq // chunk
    grid = (bsz, nchunk)
    ld = lambda b, i: (b, i, 0)
    return pl.pallas_call(
        mamba_scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, dim), ld),
            pl.BlockSpec((1, chunk, dim), ld),
            pl.BlockSpec((dim, n), lambda b, i: (0, 0)),
            pl.BlockSpec((1, chunk, n), ld),
            pl.BlockSpec((1, chunk, n), ld),
            pl.BlockSpec((dim,), lambda b, i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dim), ld),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((dim, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, dt, a, bm, c, d)
