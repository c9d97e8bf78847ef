"""Top-k token-choice MoE with capacity-based local dispatch.

Two execution paths:

* **shard_map path** (active when a mesh is installed): tokens stay local to
  their data shard and dispatch into a *local* (E, C_loc, d) capacity buffer
  -- zero dispatch communication, because activations are replicated over the
  model axis.  Expert compute is expert-parallel over the model axis when E
  divides it (arctic-480b, 128e) and d_ff-tensor-parallel otherwise
  (mixtral-8x7b, 8e on a 16-way axis); both variants finish with ONE psum
  over the model axis that simultaneously sums expert-group contributions and
  completes the TP contraction.  This exists because GSPMD's scatter
  partitioner replicates the (T*k, d) dispatch gradient -- 60 GB/device at
  arctic scale -- no matter how the operands are hinted (EXPERIMENTS.md
  sect. Perf, iteration moe-1).

* **local path** (no mesh: unit tests, single device): the same dispatch
  arithmetic without collectives.

Dropped tokens (beyond per-shard capacity) fall into a discard row -- the
standard capacity-factor trade-off, surfaced by the Switch-style aux loss.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..sharding.ctx import current_mesh
from .common import ArchConfig, Params, init_linear, init_mlp, linear, mlp


def init_moe(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    scale = d ** -0.5
    p: Params = {
        "router": init_linear(ks[0], d, e, jnp.float32),
        "wi": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale
               ).astype(cfg.dtype),
        "wg": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale
               ).astype(cfg.dtype),
        "wo": (jax.random.normal(ks[3], (e, f, d), jnp.float32)
               * f ** -0.5).astype(cfg.dtype),
    }
    if cfg.moe_dense_residual:                        # arctic-480b
        p["residual"] = init_mlp(ks[4], cfg, cfg.residual_d_ff or cfg.d_ff)
    return p


def _capacity(tokens: int, cfg: ArchConfig) -> int:
    cap = int(cfg.capacity_factor * tokens * cfg.top_k
              / max(cfg.n_experts, 1))
    return max(8, ((cap + 127) // 128) * 128)


def _dispatch_compute_combine(xf, router_w, wi, wg, wo, cfg: ArchConfig,
                              shard_idx=None) -> Tuple[jax.Array, jax.Array]:
    """Local dispatch -> expert einsums -> local combine.

    xf: (T_loc, d); wi/wg: (E_any, d, f_any); wo: (E_any, f_any, d).
    Returns (partial out (T_loc, d), aux numerator) -- caller completes any
    cross-shard reduction.
    """
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(t, cfg)

    logits = xf.astype(jnp.float32) @ router_w                   # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(gates, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux loss (local fractions).
    me = gates.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(1.0 / (t * k))
    aux = e * jnp.sum(me * ce)

    eid = topi.reshape(-1)                                       # (T*k,)
    onehot = jax.nn.one_hot(eid, e, dtype=jnp.int32)
    pos = ((jnp.cumsum(onehot, axis=0) - 1) * onehot).max(axis=-1)
    keep = pos < cap
    pos_c = jnp.where(keep, pos, cap)

    buf = jnp.zeros((e, cap + 1, d), dtype=xf.dtype)
    src = jnp.repeat(xf, k, axis=0) * keep[:, None].astype(xf.dtype)
    buf = buf.at[eid, pos_c].add(src)[:, :cap]                   # (E, C, d)

    e_loc = wi.shape[0]
    if e_loc != e:               # expert-parallel: this shard's expert slice
        shard = shard_idx if shard_idx is not None else 0
        buf = jax.lax.dynamic_slice_in_dim(buf, shard * e_loc, e_loc, 0)

    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    out_buf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo)

    if e_loc != e:               # scatter expert-group results back to E rows
        full = jnp.zeros((e, cap, d), out_buf.dtype)
        shard = shard_idx if shard_idx is not None else 0
        out_buf = jax.lax.dynamic_update_slice_in_dim(
            full, out_buf, shard * e_loc, 0)

    out_buf = jnp.concatenate(
        [out_buf, jnp.zeros((e, 1, d), out_buf.dtype)], axis=1)
    gathered = out_buf[eid, pos_c]                               # (T*k, d)
    gathered = gathered * (topw.reshape(-1, 1).astype(xf.dtype)
                           * keep[:, None].astype(xf.dtype))
    return gathered.reshape(t, k, d).sum(axis=1), aux


def moe_ffn(p: Params, x: jax.Array, cfg: ArchConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss)."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    mesh = current_mesh()

    dp_size = 1
    if mesh is not None:
        dp_size = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                               if a in mesh.axis_names]))
    if (mesh is not None and "model" in mesh.axis_names
            and (b * s) % dp_size == 0 and (b * s) >= dp_size):
        tp = mesh.shape["model"]
        dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        ep_mode = cfg.n_experts % tp == 0 and cfg.n_experts >= tp
        if ep_mode:
            wi_s = wg_s = P("model", None, None)
            wo_s = P("model", None, None)
        else:
            wi_s = wg_s = P(None, None, "model")
            wo_s = P(None, "model", None)

        def local(xl, rw, wi, wg, wo):
            shard = jax.lax.axis_index("model") if ep_mode else None
            out, aux = _dispatch_compute_combine(xl, rw, wi, wg, wo, cfg,
                                                 shard_idx=shard)
            # one psum finishes both the expert-group sum (EP) and the
            # d_ff-TP contraction (non-EP)
            out = jax.lax.psum(out, "model")
            aux = jax.lax.pmean(jax.lax.pmean(aux, "model"), dp)
            return out, aux

        out, aux = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(dp, None), P(None, None), wi_s, wg_s, wo_s),
            out_specs=(P(dp, None), P()),
            check_vma=False,
        )(xf, p["router"]["w"], p["wi"], p["wg"], p["wo"])
    else:
        out, aux = _dispatch_compute_combine(
            xf, p["router"]["w"], p["wi"], p["wg"], p["wo"], cfg)

    out = out.reshape(b, s, d)
    if cfg.moe_dense_residual:
        out = out + mlp(p["residual"], x, cfg)
    return out, aux
