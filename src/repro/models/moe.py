"""Mixture-of-Experts FFN: top-k routing, capacity-bounded dispatch.

Two execution paths share one dispatch/combine core:

  * `local` — every expert lives on every shard (smoke tests, single device).
  * `ep`    — experts sharded over the mesh "model" axis via shard_map: each
    model shard dispatches *all* of its data-shard's tokens to its local
    experts only and contributes a partial output, combined with one psum.
    Communication per layer = one [T_local, d] all-reduce (same order as a
    tensor-parallel MLP), with no all-to-all and a-priori-bounded load —
    the same load-balancing argument the paper makes for DBB blocks.

Arctic's dense-residual FFN and Kimi's shared expert are both expressed as
`dense_residual_ff` (an always-active parallel MLP).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.dist.mesh_ctx import current_mesh, data_axes_of
from repro.models.common import linear_init, normal_init
from repro.models.mlp import _ACTS, mlp_apply, mlp_init, seq_parallel_ok

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, cfg: ModelConfig, dtype) -> Dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    ks = jax.random.split(key, 6)
    scale_in = 1.0 / (d ** 0.5)
    scale_out = 1.0 / (f ** 0.5 * (2 * cfg.num_layers) ** 0.5)
    p = {
        "router": {"w": normal_init(ks[0], (d, e), scale_in, jnp.float32)},
        "experts": {
            "wi": normal_init(ks[1], (e, d, f), scale_in, dtype),
            "wo": normal_init(ks[2], (e, f, d), scale_out, dtype),
        },
    }
    if cfg.mlp_gated:
        p["experts"]["wg"] = normal_init(ks[3], (e, d, f), scale_in, dtype)
    if cfg.moe.dense_residual_ff:
        p["dense_mlp"] = mlp_init(ks[4], d, cfg.moe.dense_residual_ff, cfg,
                                  dtype)
    return p


_FUSED_EXPERT_MAX = 16


def _expert_ffn(ew: Dict, xs: jax.Array, cfg: ModelConfig) -> jax.Array:
    """xs: [E, C, d] -> [E, C, d] through per-expert gated MLP.

    On the single-device Pallas route each expert's GEMMs go through the
    dispatch registry (DESIGN.md §11) with the activation fused into the
    up-projection's final-K store — a static per-expert loop, bounded to
    small expert counts so the unrolled kernel count stays sane. The
    expert-parallel shard_map path (mesh live) keeps the batched einsums
    that GSPMD shards."""
    from repro.kernels import dispatch
    e = xs.shape[0]
    if dispatch.pallas_route_active(cfg) and e <= _FUSED_EXPERT_MAX:
        outs = []
        for i in range(e):
            h = dispatch.matmul(
                xs[i], ew["wi"][i].astype(xs.dtype),
                act="none" if cfg.mlp_gated else cfg.act,
                out_dtype=xs.dtype, cfg=cfg, pallas=True)
            if cfg.mlp_gated:
                h = dispatch.matmul(xs[i], ew["wg"][i].astype(xs.dtype),
                                    act=cfg.act, out_dtype=xs.dtype,
                                    cfg=cfg, pallas=True) * h
            outs.append(dispatch.matmul(h, ew["wo"][i].astype(xs.dtype),
                                        out_dtype=xs.dtype, cfg=cfg,
                                        pallas=True))
        return jnp.stack(outs, axis=0)
    act = _ACTS[cfg.act]
    h = jnp.einsum("ecd,edf->ecf", xs, ew["wi"].astype(xs.dtype))
    if cfg.mlp_gated:
        h = act(jnp.einsum("ecd,edf->ecf", xs, ew["wg"].astype(xs.dtype))) * h
    else:
        h = act(h)
    return jnp.einsum("ecf,efd->ecd", h, ew["wo"].astype(xs.dtype))


def _dispatch_compute_combine(
    x: jax.Array,              # [T, d] tokens on this shard
    ew: Dict,                  # expert weights, local slice [E_loc, ...]
    top_idx: jax.Array,        # [T, k] global expert ids
    top_p: jax.Array,          # [T, k] combine probabilities
    e0: int | jax.Array,       # first global expert id owned here
    e_loc: int,                # number of local experts
    capacity: int,
    cfg: ModelConfig,
) -> jax.Array:
    """Capacity-bounded sort-based dispatch for the local expert slice."""
    t, d = x.shape
    k = top_idx.shape[1]
    e_flat = top_idx.reshape(-1)
    t_flat = jnp.repeat(jnp.arange(t), k)
    p_flat = top_p.reshape(-1).astype(jnp.float32)

    local = e_flat - e0                                   # local expert id
    in_range = (local >= 0) & (local < e_loc)
    # sort by (local expert, arrival) — out-of-range keys sink to the end
    sort_key = jnp.where(in_range, local, e_loc)
    order = jnp.argsort(sort_key, stable=True)
    se, st, sp = sort_key[order], t_flat[order], p_flat[order]
    # rank of each entry within its expert group
    start = jnp.searchsorted(se, jnp.arange(e_loc))       # [E_loc]
    rank = jnp.arange(t * k) - start[jnp.clip(se, 0, e_loc - 1)]
    valid = (se < e_loc) & (rank < capacity)
    slot = jnp.where(valid, se * capacity + rank, e_loc * capacity)

    xs = jnp.zeros((e_loc * capacity + 1, d), x.dtype).at[slot].set(x[st])
    ys = _expert_ffn(ew, xs[:-1].reshape(e_loc, capacity, d), cfg)
    ys = ys.reshape(e_loc * capacity, d)
    # combine in the activation dtype: f32 combine weights keep a full
    # [T·k, d] f32 tensor live (15 GB/layer on kimi, §Perf iteration 15)
    contrib = jnp.where(valid[:, None],
                        ys[jnp.clip(slot, 0, e_loc * capacity - 1)],
                        jnp.zeros((), x.dtype)) * sp[:, None].astype(x.dtype)
    return jnp.zeros((t, d), x.dtype).at[st].add(contrib.astype(x.dtype))


def _route(x: jax.Array, router_w: jax.Array, cfg: ModelConfig,
           mean_axes: Tuple[str, ...] = (),
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (top_idx [T,k], top_p [T,k], aux_loss scalar).

    `mean_axes`: mapped axes whose token shards must be averaged *before*
    the f·P product so the Switch aux loss is the global quantity (per-shard
    products don't commute with the mean)."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)  # [T, E]
    gates = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(gates, cfg.moe.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * P_e
    e = gates.shape[-1]
    pe = gates.mean(axis=0)
    fe = jnp.zeros((e,), jnp.float32).at[top_idx.reshape(-1)].add(
        1.0 / top_idx.size)
    if mean_axes:
        pe = jax.lax.pmean(pe, mean_axes)
        fe = jax.lax.pmean(fe, mean_axes)
    aux = e * jnp.sum(fe * pe)
    return top_idx, top_p, aux


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    c = int(tokens * cfg.moe.top_k * cfg.moe.capacity_factor
            / max(1, cfg.moe.num_experts))
    return max(8, -(-c // 8) * 8)       # round up to sublane multiple


def moe_apply(p: Dict, cfg: ModelConfig, x: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y [B, S, d], aux_loss). Picks local vs EP path."""
    b, s, d = x.shape
    mesh = current_mesh()
    e = cfg.moe.num_experts
    impl = cfg.moe.impl
    if impl == "auto":
        ep_ok = (mesh is not None and "model" in mesh.axis_names
                 and mesh.shape["model"] > 1 and e % mesh.shape["model"] == 0)
        impl = "ep" if ep_ok else "local"

    router_w = p["router"]["w"]
    if impl == "local":
        xt = x.reshape(b * s, d)
        top_idx, top_p, aux = _route(xt, router_w, cfg)
        y = _dispatch_compute_combine(
            xt, p["experts"], top_idx, top_p, 0, e,
            _capacity(b * s, cfg), cfg)
        y = y.reshape(b, s, d)
    else:
        tp = mesh.shape["model"]
        e_loc = e // tp
        daxes = data_axes_of(mesh)
        denom = 1                      # tokens per (pod × data) shard
        for a in daxes:
            denom *= mesh.shape[a]
        t_local = (b * s) // denom
        cap = _capacity(t_local, cfg)

        sp = seq_parallel_ok(cfg, s, tp)
        # token-chunked dispatch (§Perf iteration 16): the [T·k, d] gather
        # is real HBM on any backend — scanning 16k-token chunks caps it at
        # [chunk·k, d] with per-chunk capacity (equal chunks ⇒ the batched
        # aux statistics are exact)
        chunk_tokens = 16_384

        def shard_fn(xl, rw, ew):
            if sp:      # SP: gather sequence shards at block entry
                xl = jax.lax.all_gather(xl, "model", axis=1, tiled=True)
            bl, sl = xl.shape[0], xl.shape[1]
            t_all = bl * sl
            xt = xl.reshape(t_all, d)
            midx = jax.lax.axis_index("model")
            nc = max(1, t_all // chunk_tokens)
            while t_all % nc:
                nc -= 1
            t_c = t_all // nc
            cap_c = _capacity(t_c, cfg)

            @jax.checkpoint
            def one(carry, xc):
                aux_acc = carry
                top_idx, top_p, aux = _route(xc, rw, cfg, mean_axes=daxes)
                yc = _dispatch_compute_combine(
                    xc, ew, top_idx, top_p, midx * e_loc, e_loc, cap_c, cfg)
                return aux_acc + aux, yc

            aux0 = jnp.zeros((), jnp.float32)
            if nc == 1:
                aux, y = one(aux0, xt)
            else:
                aux, y = jax.lax.scan(one, aux0, xt.reshape(nc, t_c, d))
                aux = aux / nc
                y = y.reshape(t_all, d)
            y = y.reshape(bl, sl, d)
            if sp:      # reduce-scatter back to the seq-sharded residual
                y = jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                         tiled=True)
            else:
                y = jax.lax.psum(y, "model")
            return y, aux

        ba = daxes if daxes else None
        batch_spec = P(ba, "model", None) if sp else P(ba)
        y, aux = shard_map(
            shard_fn, mesh=mesh,
            in_specs=(batch_spec, P(), P("model")),
            out_specs=(batch_spec, P()),
            check_vma=False,
        )(x, router_w, p["experts"])
        # aux is already pmean'd over model; the per-data-shard mean folds
        # into the global loss mean through the data-parallel grad psum.

    if "dense_mlp" in p:
        y = y + mlp_apply(p["dense_mlp"],
                          cfg.replace(d_ff=cfg.moe.dense_residual_ff), x)
    return y, aux
