#!/usr/bin/env python3
"""Chip smoke test: the stencil engine's main path on a TPU, checked exactly.

    python chip_smoke.py [--seed N]          # one chip, 512^3 f32 domain
    python chip_smoke.py --four-chips        # a 4-chip host: sharded phase only

One chip: every call goes through the public entry points with the default
``interpret=None`` and ``auto`` choices on a 512 x 512 x 512 f32 field (one
field is 0.5 GiB): ``stencil_apply`` stencil27 under clamp and periodic BCs,
``stencil_sweep_driver`` at 4 sweeps in its ``auto`` mode and forced to the
wavefront, ``stencil_apply`` star13 (radius 2), one bf16 stencil27 call, and
one guarded call that must finish on its first rung.

Four chips: a 1024 x 512 x 512 f32 field (0.5 GiB per chip) through
``stencil_sharded`` -- the i-chain on a (4,) mesh under clamp and periodic
BCs, and the (2, 2, 1) grid with ``overlap="off"`` and ``"on"``.  Every
requested axis must be sharded, and the overlap run must carry its two
boundary-strip kernels.

Each result is compared with ``==`` against a plain ``jnp`` reference
written here (zero ghosts padded ``np.pad``-style, a sum of shifted slices,
the clamp ring zeroed once per sweep).  Data and weights are integers in
{-1, 0, 1} made from ``--seed``, so every partial sum is exact in f32
(|value| <= 27^4 < 2^24 after 4 sweeps) and in bf16 after one sweep.  Each
call's compiled program must hold a ``tpu_custom_call``: an interpret-mode
run fails.  Lines before the last are informative (compile times, wall
times around ``block_until_ready`` after a warm-up, the bytes the algorithm
needs -- one read and one write of the field): smoke timings, not
benchmark metrics.  The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failure exits non-zero before printing it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.kernels import (autotune_sweeps, compile_plan,  # noqa: E402
                           last_guard_report, stencil_apply, stencil_sharded,
                           stencil_sweep_driver)
from repro.sharding.planner import (stencil_grid_sharding,  # noqa: E402
                                    stencil_halo_sharding)

N = 512                      # one-chip domain edge: 512^3 f32 = 0.5 GiB
FOUR_CHIP_SHAPE = (1024, 512, 512)
REPS = 3
KERNEL = 'custom_call_target="tpu_custom_call"'


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def info(msg: str) -> None:
    print(f"info: {msg}", flush=True)


# ---------------------------------------------------------------------------
# The reference: independent of the engine's plan IR.
# ---------------------------------------------------------------------------

def taps(name: str, w: np.ndarray):
    """((di, dj, dk), weight) for every tap, from the documented weight
    layouts: stencil27 ``w[|di|, |dj|, |dk|]``; star13 ``w[d]`` at distance
    ``d`` along one axis (``w[0]`` the centre)."""
    if name == "stencil27":
        return [((di, dj, dk), float(w[abs(di), abs(dj), abs(dk)]))
                for di in (-1, 0, 1) for dj in (-1, 0, 1) for dk in (-1, 0, 1)]
    if name == "star13":
        out = [((0, 0, 0), float(w[0]))]
        for ax in range(3):
            for d in (-2, -1, 1, 2):
                off = [0, 0, 0]
                off[ax] = d
                out.append((tuple(off), float(w[abs(d)])))
        return out
    raise ValueError(name)


def ref_sweeps(u: jax.Array, tap_list, radius: int, periodic: bool,
               sweeps: int = 1) -> jax.Array:
    """``sweeps`` Jacobi applications in f32: pad by ``radius`` (zeros, or
    wrapped for periodic), sum the weighted shifted slices, and zero the
    one-point boundary ring (clamp)."""
    u = u.astype(jnp.float32)
    shape = u.shape
    ring = None
    if not periodic:
        ring = jnp.ones(shape, bool)
        for ax in range(3):
            idx = jax.lax.broadcasted_iota(jnp.int32, shape, ax)
            ring &= (idx > 0) & (idx < shape[ax] - 1)
    for _ in range(sweeps):
        up = jnp.pad(u, radius, mode="wrap" if periodic else "constant")
        out = jnp.zeros(shape, jnp.float32)
        for (di, dj, dk), wt in tap_list:
            if wt != 0.0:
                out = out + wt * up[radius + di:radius + di + shape[0],
                                    radius + dj:radius + dj + shape[1],
                                    radius + dk:radius + dk + shape[2]]
        u = out if ring is None else jnp.where(ring, out, 0.0)
    return u


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------

def compile_call(name: str, fn, *args):
    """Compile ``fn`` for ``args``; the program must hold a Pallas kernel.
    Returns (compiled, seconds, kernel count)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t0
    n_kernels = compiled.as_text().count(KERNEL)
    check(n_kernels > 0, f"{name}: compiled program holds no tpu_custom_call "
                         f"(Pallas ran in interpret mode)")
    return compiled, secs, n_kernels


def time_call(compiled, *args):
    """Warm up once, then wall seconds of REPS calls, each ended by
    ``block_until_ready``."""
    out = compiled(*args)
    out.block_until_ready()
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = compiled(*args)
        out.block_until_ready()
        times.append(time.perf_counter() - t0)
    return out, times


def run_phase(name: str, fn, args, want, extra: str = "") -> int:
    """Compile, time and check one call; returns its kernel count.  The
    bytes the algorithm needs are one read and one write of the field."""
    compiled, t_compile, n_kernels = compile_call(name, fn, *args)
    got, times = time_call(compiled, *args)
    check(got.shape == want.shape and got.dtype == want.dtype,
          f"{name}: result {got.shape} {got.dtype}, reference {want.shape} "
          f"{want.dtype}")
    bad = int(jnp.sum(got != want))
    check(bad == 0, f"{name}: {bad} points differ from the jnp reference")
    med = statistics.median(times)
    need = 2 * want.dtype.itemsize * want.size
    info(f"{name}: exact vs reference; kernels={n_kernels} "
         f"compile_s={t_compile!r} wall_s={times!r} median_s={med!r} "
         f"needed_bytes={need} needed_GBps={need / med / 1e9!r}"
         + (f" {extra}" if extra else ""))
    return n_kernels


def int_field(key, shape, dtype=jnp.float32, sharding=None):
    """Integer values in {-1, 0, 1}, made on the device."""
    def make(k):
        return jax.random.randint(k, shape, -1, 2).astype(dtype)
    return jax.jit(make, out_shardings=sharding)(key)


def one_chip(seed: int) -> None:
    shape = (N, N, N)
    rng = np.random.default_rng(seed)
    w27 = rng.integers(-1, 2, (2, 2, 2)).astype(np.float32)
    w13 = rng.integers(-1, 2, (3,)).astype(np.float32)
    a = int_field(jax.random.PRNGKey(seed), shape)
    a_bf16 = a.astype(jnp.bfloat16)
    t27, t13 = taps("stencil27", w27), taps("star13", w13)
    w27d, w13d = jnp.asarray(w27), jnp.asarray(w13)
    ref = jax.jit(ref_sweeps, static_argnums=(1, 2, 3, 4))

    want = ref(a, tuple(t27), 1, False, 1)
    run_phase("stencil_apply stencil27 clamp f32",
              lambda x, w: stencil_apply(x, w, "stencil27"), (a, w27d), want)

    # One guarded call on the same program: it must pass its checks on the
    # first rung, with no fault, demotion or blacklisting.
    t0 = time.perf_counter()
    got = stencil_apply(a, w27d, "stencil27", guard="invariant")
    got.block_until_ready()
    secs = time.perf_counter() - t0
    rep = last_guard_report()
    check(rep is not None and rep.final == rep.start and not rep.demotions
          and not rep.blacklisted
          and all(att["fault"] is None for att in rep.attempts),
          f"guarded stencil_apply left its first rung: "
          f"{rep.describe() if rep is not None else None}")
    check(int(jnp.sum(got != want)) == 0,
          "guarded stencil_apply differs from the jnp reference")
    info(f"guarded stencil_apply stencil27 (guard=invariant): exact vs "
         f"reference; final rung {rep.final!r}, no demotion; "
         f"wall_s={secs!r} (one call, checks included)")
    del got, want

    want = ref(a, tuple(t27), 1, True, 1)
    run_phase("stencil_apply stencil27 periodic f32",
              lambda x, w: stencil_apply(x, w, "stencil27", bc="periodic"),
              (a, w27d), want)
    del want

    want = ref(a, tuple(t27), 1, False, 4)
    sel = autotune_sweeps(*shape, 4, 4, compile_plan("stencil27"))
    run_phase("stencil_sweep_driver stencil27 sweeps=4 mode=auto f32",
              lambda x, w: stencil_sweep_driver(x, w, "stencil27", sweeps=4),
              (a, w27d), want,
              extra=f"auto_mode={sel.mode} path={sel.path} "
                    f"blocks=({sel.block_i}, {sel.block_j})")
    run_phase("stencil_sweep_driver stencil27 sweeps=4 mode=wavefront f32",
              lambda x, w: stencil_sweep_driver(x, w, "stencil27", sweeps=4,
                                                mode="wavefront"),
              (a, w27d), want)
    del want

    want = ref(a, tuple(t13), 2, False, 1)
    run_phase("stencil_apply star13 clamp f32",
              lambda x, w: stencil_apply(x, w, "star13"), (a, w13d), want)
    del want

    want = ref(a, tuple(t27), 1, False, 1).astype(jnp.bfloat16)
    run_phase("stencil_apply stencil27 clamp bf16",
              lambda x, w: stencil_apply(x, w, "stencil27"), (a_bf16, w27d),
              want)


def auto_mesh(shape, names):
    """A device mesh whose axes leave sharding propagation to the compiler
    (the reference's pad and slices run on the sharded global array)."""
    from jax.sharding import AxisType
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(shape))


def four_chips(seed: int) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P
    shape = FOUR_CHIP_SHAPE
    rng = np.random.default_rng(seed)
    w27 = rng.integers(-1, 2, (2, 2, 2)).astype(np.float32)
    t27 = tuple(taps("stencil27", w27))
    w = jnp.asarray(w27)
    ref = jax.jit(ref_sweeps, static_argnums=(1, 2, 3, 4))

    chain = auto_mesh((4,), ("i",))
    a = int_field(jax.random.PRNGKey(seed), shape,
                  sharding=NamedSharding(chain, P("i", None, None)))
    for bc in ("clamp", "periodic"):
        plan = stencil_halo_sharding(shape[0], chain, axis="i", sweeps=1,
                                     radius=1, periodic=bc == "periodic")
        check(plan.n_shards == 4,
              f"chain {bc}: planner did not shard i 4 ways: {plan.notes}")
        want = ref(a, t27, 1, bc == "periodic", 1)
        run_phase(f"stencil_sharded stencil27 i-chain (4,) {bc} f32",
                  lambda x, w_, plan=plan, bc=bc: stencil_sharded(
                      x, w_, "stencil27", mesh=chain, axis="i", bc=bc,
                      shard_plan=plan),
                  (a, w), want)
        del want
    del a

    grid = auto_mesh((2, 2), ("x", "y"))
    axes = ("x", "y", None)
    gplan = stencil_grid_sharding(shape, grid, axes=axes, sweeps=1)
    check(gplan.axes == axes and gplan.n_shards == (2, 2, 1),
          f"grid: planner did not shard (i, j) 2 x 2: {gplan.notes}")
    a = int_field(jax.random.PRNGKey(seed), shape,
                  sharding=NamedSharding(grid, P("x", "y", None)))
    want = ref(a, t27, 1, False, 1)
    for overlap in ("off", "on"):
        name = f"stencil_sharded stencil27 grid (2, 2, 1) overlap={overlap} f32"
        fn = (lambda x, w_, overlap=overlap: stencil_sharded(
            x, w_, "stencil27", mesh=grid, axes=axes, shard_plan=gplan,
            overlap=overlap))
        n_kernels = run_phase(name, fn, (a, w), want)
        # overlap="on" adds the two boundary-strip kernels to the interior
        # one; a serialized fallback would compile a single kernel.
        check(n_kernels == (3 if overlap == "on" else 1),
              f"{name}: {n_kernels} kernels in the program; overlap "
              f"{'did not engage' if overlap == 'on' else 'is unexpected'}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase on a 4-chip host")
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}); this check runs on the chip only",
              file=sys.stderr)
        return 2
    want_count = 4 if args.four_chips else 1
    if len(devs) < want_count:
        print(f"chip_smoke: {want_count} chips needed, JAX sees {len(devs)}",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    cache_events = {"hits": 0, "writes": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["writes"] += 1      # recorded when an entry is written

    jax.monitoring.register_event_listener(on_event)
    info(f"device kind={dev.device_kind!r} count={len(devs)} "
         f"jax={jax.__version__} seed={args.seed} compile_cache={cache}")
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    info(f"total_s={time.perf_counter() - t0!r} persistent_cache "
         f"hits={cache_events['hits']} writes={cache_events['writes']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
