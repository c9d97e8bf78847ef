"""Jitted public entry point for the Pallas flash-attention kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel import flash_attention_kernel


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int | None = None,
                    q_offset: int = 0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = True) -> jax.Array:
    """q: (B, H, Lq, D); k, v: (B, Hkv, Lk, D); returns (B, H, Lq, D)."""
    b, h, lq, dh = q.shape
    _, hkv, lk, _ = k.shape
    assert h % hkv == 0, "query heads must be a multiple of kv heads"
    group = h // hkv
    bq = min(block_q, lq)
    bk = min(block_k, lk)
    if lq % bq or lk % bk:
        raise ValueError(f"blocks ({bq},{bk}) must divide (Lq,Lk)=({lq},{lk})")
    nk = lk // bk
    grid = (b, h, lq // bq, nk)
    kernel = functools.partial(
        flash_attention_kernel, scale=1.0 / (dh ** 0.5), causal=causal,
        window=window, q_offset=q_offset, bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, iq, ik, g=group: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, iq, ik, g=group: (b_, h_ // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
