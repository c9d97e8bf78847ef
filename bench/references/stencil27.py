"""Plain ``jnp`` reference of the 27-point operator, independent of the engine.

Copied from the engine's chip smoke check, not imported: the yardstick must
not move when the program does.  ``w`` follows the documented stencil27
layout, ``w[|di|, |dj|, |dk|]``.  ``clamp`` pads with zero ghosts and zeroes
the one-point boundary ring after every sweep; ``periodic`` wraps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

RADIUS = 1


def taps(w):
    """((di, dj, dk), weight) for each of the 27 taps."""
    return tuple(((di, dj, dk), float(w[abs(di)][abs(dj)][abs(dk)]))
                 for di in (-1, 0, 1) for dj in (-1, 0, 1)
                 for dk in (-1, 0, 1))


def _sweeps(u, tap_list, periodic: bool, sweeps: int):
    u = u.astype(jnp.float32)
    shape = u.shape
    ring = None
    if not periodic:
        ring = jnp.ones(shape, bool)
        for ax in range(3):
            idx = jax.lax.broadcasted_iota(jnp.int32, shape, ax)
            ring &= (idx > 0) & (idx < shape[ax] - 1)
    r = RADIUS
    for _ in range(sweeps):
        up = jnp.pad(u, r, mode="wrap" if periodic else "constant")
        out = jnp.zeros(shape, jnp.float32)
        for (di, dj, dk), wt in tap_list:
            if wt != 0.0:
                out = out + wt * up[r + di:r + di + shape[0],
                                    r + dj:r + dj + shape[1],
                                    r + dk:r + dk + shape[2]]
        u = out if ring is None else jnp.where(ring, out, 0.0)
    return u


_sweeps_jit = jax.jit(_sweeps, static_argnums=(1, 2, 3))


def reference(u: jax.Array, w, bc: str, sweeps: int) -> jax.Array:
    """``sweeps`` applications of the operator to ``u``, in f32."""
    if bc not in ("clamp", "periodic"):
        raise ValueError(f"stencil27 reference: unsupported bc {bc!r}")
    return _sweeps_jit(u, taps(w), bc == "periodic", sweeps)
