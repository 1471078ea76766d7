"""Gated / plain MLP blocks (the main DBB surface in every architecture).

Two execution paths:
  * GSPMD (default, single-device tests): plain matmuls, the partitioner
    inserts collectives.
  * explicit-TP (`_mlp_tp`, picked when a mesh with a model axis is live
    and d_ff divides): Megatron column→row parallel inside one shard_map,
    so the boundary psum runs on the *storage dtype* (bf16). GSPMD's own
    placement reduced the f32 dot outputs — 2× the wire bytes for no
    benefit (§Perf iteration 5; ~130 GB/step on qwen train_4k).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.core.dbb import DbbWeight
from repro.dist.mesh_ctx import current_mesh, data_axes_of, shard_tp
from repro.models.common import linear_init, use_fused_gemm

__all__ = ["mlp_init", "mlp_apply"]

_ACTS = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}


def mlp_init(key, d: int, f: int, cfg: ModelConfig, dtype) -> Dict:
    ks = jax.random.split(key, 3)
    p = {"wi": linear_init(ks[0], d, f, dtype),
         "wo": linear_init(ks[1], f, d, dtype,
                           scale=1.0 / (f ** 0.5 * (2 * cfg.num_layers) ** 0.5))}
    if cfg.mlp_gated:
        p["wg"] = linear_init(ks[2], d, f, dtype)
    return p


def batch_axes_for(mesh, batch: int):
    daxes = data_axes_of(mesh)
    for k in range(len(daxes), 0, -1):
        n = 1
        for a in daxes[:k]:
            n *= mesh.shape[a]
        if batch % n == 0:
            return daxes[:k] if k > 1 else daxes[0]
    return None


def _tp_size(mesh) -> int:
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return mesh.shape["model"]


def _fused_gemm(x: jax.Array, pp: Dict, act: str,
                cfg: ModelConfig) -> jax.Array:
    """One fused-epilogue GEMM against a dense or DBB-packed weight —
    `kernels.dispatch` owns the route: packed weights (decode fast path,
    DESIGN.md §9) stream compressed through the DBB kernels, dense ones
    take the STA kernels, skinny vs M-tiled by the registry's cost model
    (§11)."""
    from repro.kernels import dispatch
    return dispatch.matmul(x, pp["w"], pp.get("b"), act=act,
                           out_dtype=x.dtype, cfg=cfg, pallas=True)


def _dense_w(pp: Dict, dtype) -> jax.Array:
    """Dense weight for the XLA path; DbbWeight leaves (which only the
    fused route is supposed to see) expand as a safety net."""
    w = pp["w"]
    if isinstance(w, DbbWeight):
        from repro.core.dbb_linear import decompress_xla
        return decompress_xla(w, dtype=dtype)
    return w.astype(dtype)


def _mlp_fused(p: Dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Single-device serving path: every GEMM through the STA Pallas kernel
    (dense weights) or the DBB kernel (packed weights stream compressed),
    the activation fused into the up-projection's final-K store (DESIGN.md
    §7) — the [tokens, d_ff] pre-activation never round-trips through HBM.
    Gated MLPs fuse the act into the gate GEMM and multiply elementwise."""
    h = _fused_gemm(x, p["wi"], "none" if cfg.mlp_gated else cfg.act, cfg)
    if cfg.mlp_gated:
        h = _fused_gemm(x, p["wg"], cfg.act, cfg) * h
    return _fused_gemm(h, p["wo"], "none", cfg)


def _mlp_dense(p: Dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    from jax.ad_checkpoint import checkpoint_name
    if use_fused_gemm(cfg):
        return _mlp_fused(p, cfg, x)
    act = _ACTS[cfg.act]
    # named for the selective-remat policy (§Perf iteration 8): saving the
    # two fat up-projections skips their recompute in the backward pass at
    # ~56 MB/layer/shard — the best flops-per-byte save in the block
    h = checkpoint_name(x @ _dense_w(p["wi"], x.dtype), "mlp_wi")
    if cfg.mlp_gated:
        h = act(checkpoint_name(x @ _dense_w(p["wg"], x.dtype),
                                "mlp_wg")) * h
    else:
        h = act(h)
    return h @ _dense_w(p["wo"], x.dtype)


def seq_parallel_ok(cfg: ModelConfig, seq: int, tp: int) -> bool:
    """Megatron-SP eligibility: standard transformer stacks whose sequence
    divides the model axis (hybrid SSM stacks keep full-seq residuals —
    the recurrence would need halo exchanges)."""
    return (cfg.parallel != "dp"
            and cfg.family in ("dense_lm", "moe_lm", "vlm_lm", "audio_lm")
            and seq % tp == 0 and seq > tp)


def mlp_apply(p: Dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    stp = shard_tp()
    if stp > 1:
        # Already inside a TP shard_map body (the serving wrapper,
        # DESIGN.md §14): wi/wg arrive column-sharded, wo row-sharded per
        # the param specs, so `_mlp_dense` runs the per-shard Pallas
        # kernels on local slices and one chunked boundary all-reduce
        # completes the block (issued per chunk so XLA's async scheduler
        # overlaps wire time with the epilogue stores). No nested
        # shard_map — collectives bind to the enclosing mesh axes.
        from repro.dist.collectives import overlapped_psum
        return overlapped_psum(_mlp_dense(p, cfg, x), "model")
    mesh = current_mesh()
    tp = _tp_size(mesh) if cfg.parallel != "dp" else 1
    wi = p["wi"]["w"]
    f = wi.n_dim if isinstance(wi, DbbWeight) else wi.shape[-1]
    if tp > 1 and f % tp == 0 and x.ndim == 3:
        ba = batch_axes_for(mesh, x.shape[0])
        sp = seq_parallel_ok(cfg, x.shape[1], tp)
        wspecs = {"wi": {"w": P(None, "model")},
                  "wo": {"w": P("model", None)}}
        if cfg.mlp_gated:
            wspecs["wg"] = {"w": P(None, "model")}
        xspec = P(ba, "model", None) if sp else P(ba, None, None)

        def fn(xl, pl):
            if sp:      # gather the sequence shards at block entry (SP)
                xl = jax.lax.all_gather(xl, "model", axis=1, tiled=True)
            y = _mlp_dense(pl, cfg, xl)      # local f-slice, partial on d
            if sp:      # reduce-scatter back to the seq-sharded residual
                return jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                            tiled=True)
            return jax.lax.psum(y, "model")  # bf16 boundary reduce

        return shard_map(
            fn, mesh=mesh,
            in_specs=(xspec, wspecs),
            out_specs=xspec,
            check_vma=False)(x, {k: p[k] for k in wspecs})
    return _mlp_dense(p, cfg, x)
