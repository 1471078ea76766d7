"""DbbLinear: one linear layer, three execution paths.

Training      : dense master weights; the train loop applies the DBB
                straight-through projection to the whole param tree
                (core/sparsity.py), so model code stays plain ``x @ w``.
Serving (TPU) : weights stored packed (`DbbWeight`); matmul routes through
                the DBB Pallas kernels via `kernels.dispatch` —
                decompression happens in VMEM.
Serving (XLA) : distributed graphs (and the CPU dry-run) use the pure-XLA
                path: packed weights live in HBM, `decompress_xla` expands
                them inside the jitted step, and GSPMD shards the dense
                matmul. Weight HBM *residency* is the compressed 62.5%.

`maybe_decompress_tree` converts a packed param tree to dense inside a jit;
`pack_tree` converts trained dense params to packed serving params.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config import DbbConfig
from repro.core.dbb import DbbWeight, pack_dbb
from repro.core.sparsity import dbb_eligible, _path_str

__all__ = ["dbb_linear_apply", "decompress_xla", "pack_tree",
           "maybe_decompress_tree", "tree_footprint_bytes",
           "DECOMPRESS_STATS"]

# Trace-time instrumentation: every decompress_xla call (i.e. every place a
# dense copy of a packed weight is materialized inside a jitted graph)
# increments this counter at trace time. The decode benchmark and the
# fast-path tests assert the counter stays flat while tracing the packed
# streaming decode step — the structural proof that no stacked layer weight
# ever expands to dense (DESIGN.md §9).
DECOMPRESS_STATS = {"calls": 0}


def decompress_xla(p: DbbWeight, dtype=None) -> jax.Array:
    """Pure-XLA decompression (GSPMD-shardable). Handles stacked leaves
    ([L, Kc, N] scan stacks and [E, Kc, N] expert stacks) by vmapping.
    ``bits=4`` leaves dequantize through the groupwise scale plane and
    come back f32 (DESIGN.md §16)."""
    from repro.kernels import decompress_ref, decompress_w4_ref
    DECOMPRESS_STATS["calls"] += 1
    if p.bits == 4:
        def one4(values, bitmask, gscale):
            return decompress_w4_ref(values, bitmask.astype(jnp.int32),
                                     gscale, block=p.block, nnz=p.nnz,
                                     group=p.group)
        fn = one4
        for _ in range(p.values.ndim - 2):
            fn = jax.vmap(fn)
        w = fn(p.values, p.bitmask, p.scale)
        return w.astype(dtype) if dtype is not None else w
    def one(values, bitmask):
        return decompress_ref(values, bitmask.astype(jnp.int32),
                              block=p.block, nnz=p.nnz)
    values, bitmask = p.values, p.bitmask
    fn = one
    for _ in range(values.ndim - 2):
        fn = jax.vmap(fn)
    w = fn(values, bitmask)
    if p.scale is not None:
        w = w * p.scale[..., None, :]
    return w.astype(dtype) if dtype is not None else w


def dbb_linear_apply(x: jax.Array, w, bias=None, *, act: str = "none",
                     impl: str = "xla", out_dtype=None,
                     cfg=None) -> jax.Array:
    """``act(x @ w + bias)`` where w is dense or a DbbWeight, routed by the
    kernel dispatch registry (DESIGN.md §11).

    impl="pallas" activates the fused-kernel route family: the registry
    picks skinny/M-tiled STA for dense weights and skinny/M-tiled DBB for
    packed ones (bias/act and the DbbWeight per-channel scale fuse into
    the kernel epilogue — one HBM store of the finished output, DESIGN.md
    §7). impl="xla" keeps separate post-matmul ops, which GSPMD can shard.
    ``cfg`` (optional) supplies `kernel_routes` overrides.
    """
    from repro.kernels import dispatch
    return dispatch.matmul(x, w, bias, act=act, out_dtype=out_dtype,
                           cfg=cfg, pallas=(impl == "pallas"))


def pack_tree(params: Any, cfg: DbbConfig, quantize: bool = False) -> Any:
    """Pack every DBB-eligible dense leaf into DbbWeight (serving format).

    Stacked leaves [..., K, N] pack along their K axis; `quantize=True`
    stores INT8 values with per-out-channel scales — the paper's exact
    deployment format (INT8 operands + bitmask + 4 value bytes per 8)."""
    if not cfg.enabled:
        return params

    def visit(path, leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim < 2:
            return leaf
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        if not dbb_eligible(_path_str(path), cfg):
            return leaf
        kd = leaf.shape[-2]
        if kd % cfg.block != 0:
            return leaf
        # sub-8-bit plane (DESIGN.md §16): only where the w4 format's
        # divisibility holds — other leaves stay INT8-packed
        w4 = (cfg.weight_bits == 4
              and cfg.quant_group > 0
              and cfg.quant_group % cfg.block == 0
              and kd % cfg.quant_group == 0
              and (kd // cfg.block * cfg.nnz) % 2 == 0)

        def pack_one(w):
            if w4:
                return pack_dbb(w.astype(jnp.float32), cfg.block, cfg.nnz,
                                bits=4, group=cfg.quant_group)
            if quantize:
                from repro.core.quant import quantize_weight
                qw = quantize_weight(w.astype(jnp.float32))
                p = pack_dbb(qw.q, cfg.block, cfg.nnz)
                return DbbWeight(values=p.values.astype(jnp.int8),
                                 indices=p.indices, bitmask=p.bitmask,
                                 scale=qw.scale, block=cfg.block,
                                 nnz=cfg.nnz, k_dim=kd)
            return pack_dbb(w, cfg.block, cfg.nnz)

        fn = pack_one
        for _ in range(leaf.ndim - 2):
            fn = jax.vmap(fn)
        p = jax.jit(fn)(leaf)      # one fused program: bounded temporaries
        # serving format drops the diagnostic int32 indices (4 B/value —
        # 4x the int8 payload); kernels and decompress consume the bitmask
        return DbbWeight(values=p.values, indices=None,
                         bitmask=p.bitmask, scale=p.scale,
                         block=cfg.block, nnz=cfg.nnz, k_dim=kd,
                         bits=4 if w4 else 8,
                         group=cfg.quant_group if w4 else 0)

    return jax.tree_util.tree_map_with_path(
        visit, params, is_leaf=lambda x: isinstance(x, DbbWeight))


def maybe_decompress_tree(params: Any, dtype=None) -> Any:
    """Expand every DbbWeight leaf to dense (call inside the jitted step so
    HBM residency stays compressed)."""
    def visit(leaf):
        if isinstance(leaf, DbbWeight):
            return decompress_xla(leaf, dtype=dtype)
        return leaf
    return jax.tree_util.tree_map(
        visit, params, is_leaf=lambda x: isinstance(x, DbbWeight))


def tree_footprint_bytes(params: Any) -> int:
    """HBM residency of a (possibly packed) param tree.

    DbbWeight leaves count values + 1 mask byte per block (the paper's
    storage format), not the diagnostic int32 arrays.
    """
    total = 0

    def visit(leaf):
        nonlocal total
        if isinstance(leaf, DbbWeight):
            # bitmask.size counts (block, col) pairs directly — values.size
            # over nnz would undercount on w4 leaves (nibble-packed rows)
            nb = leaf.bitmask.size
            total += leaf.values.size * leaf.values.dtype.itemsize
            total += nb * ((leaf.block + 7) // 8)
            if leaf.scale is not None:
                total += leaf.scale.size * leaf.scale.dtype.itemsize
        elif hasattr(leaf, "size"):
            total += leaf.size * leaf.dtype.itemsize
        return leaf

    jax.tree_util.tree_map(visit, params,
                           is_leaf=lambda x: isinstance(x, DbbWeight))
    return total
