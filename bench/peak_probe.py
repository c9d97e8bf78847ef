#!/usr/bin/env python3
"""Measure the chip's vector f32 rate and its sustained HBM bandwidth.

    python3 bench/peak_probe.py [--seconds 2]

The vector rate, for ``peaks.json``: a Pallas kernel keeps a block of f32
values in registers and runs ``x = x * a + b`` on it, unrolled, many times;
each multiply-add counts 2 flops.  Several block heights are tried and the
fastest is the rate, since none of them waits on memory.  The bandwidth, as
a check on the published peak: a plain XLA ``x * a + b`` over a 1 GiB field,
one read and one write per call.  Both are timed by the host clock over at
least ``--seconds`` of back-to-back calls.  One JSON line per reading.
Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
UNROLL = 16
STEPS = 2048                # loop trips per block
GRID = 64                   # blocks per call


def _madd_kernel(x_ref, o_ref):
    def body(_, x):
        for _ in range(UNROLL):
            x = x * 0.999 + 0.001
        return x
    o_ref[...] = jax.lax.fori_loop(0, STEPS, body, x_ref[...])


@functools.partial(jax.jit, static_argnums=1)
def _madd(x, rows):
    spec = pl.BlockSpec((rows, LANES), lambda g: (g, 0))
    return pl.pallas_call(
        _madd_kernel, grid=(GRID,), in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)


@jax.jit
def _stream(x):
    return x * 0.999 + 0.001


def rate(fn, x, work: float, seconds: float) -> float:
    """``work`` per call over the mean time of back-to-back calls."""
    fn(x).block_until_ready()             # compile and warm up
    calls, start = 0, time.perf_counter()
    while True:
        fn(x).block_until_ready()
        calls += 1
        spent = time.perf_counter() - start
        if spent >= seconds:
            return work * calls / spent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"peak_probe: no TPU ({dev.platform})", file=sys.stderr)
        return 2
    best = 0.0
    for rows in (64, 128, 256):
        x = jnp.ones((GRID * rows, LANES), jnp.float32)
        flops = 2.0 * x.size * UNROLL * STEPS
        r = rate(lambda a: _madd(a, rows), x, flops, args.seconds)
        best = max(best, r)
        print(json.dumps({"probe": "vector_madd_f32", "rows": rows,
                          "flops_per_s": r}), flush=True)
    field = jnp.ones((1024, 512, 512), jnp.float32)
    bw = rate(_stream, field, 2.0 * field.nbytes, args.seconds)
    print(json.dumps({"probe": "hbm_stream_f32", "bytes": field.nbytes,
                      "bytes_per_s": bw}), flush=True)
    print(json.dumps({"device_kind": dev.device_kind,
                      "vector_f32_flops_per_s": best,
                      "hbm_stream_bytes_per_s": bw}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
