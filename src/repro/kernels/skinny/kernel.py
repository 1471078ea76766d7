"""Skinny-M weight-streaming GEMM kernels — the decode fast path
(DESIGN.md §9).

Decode GEMMs are GEMV-shaped: M = batch rows (1-32), K = d_model,
N = d_ff / vocab. They sit deep in the memory-bound regime, so wall time is
weight bytes / HBM bandwidth and the tiled kernels' M-grid machinery is pure
overhead. These kernels restructure the loop for that regime:

  * the whole [M, K] activation row-block is **resident in VMEM** for the
    kernel's lifetime (constant index map — fetched once, never re-read);
  * the grid is **N-major** with K innermost: only the weight stream moves,
    tile after tile, through the K loop — the TPU analogue of the paper's
    weight-stationary streaming for the bandwidth-bound regime
    (arXiv:2009.02381);
  * the DBB variant streams the *compressed* values + bitmask (62.5% of
    dense bytes at k=4/B=8) and decompresses in VMEM right before the MXU
    dot — the dense weight never exists anywhere, HBM included;
  * the shared bias/activation/requant epilogue (DESIGN.md §7) runs on the
    accumulator tile in the final-K store, identical to the tiled kernels.

Shape contract (pad at the ops layer):
    x [M, K] resident, M % SUBLANE == 0, M <= SKINNY_M_MAX after padding
    w [K, N] dense  or  values [K/B·k, N] + bitmask [K/B, N] compressed
    K % block_k == 0, N % block_n == 0 (and block_k % B == 0 for DBB)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sta import SUBLANE
from repro.kernels.common import (SKINNY_M_MAX, CompilerParams, acc_dtype_for,
                                  pltpu, round_up, skinny_ok)
from repro.kernels.dbb_gemm.kernel import (_decompress_tile, _dequant_tile,
                                          group_scale_rows)
from repro.kernels.epilogue import Epilogue, apply_epilogue, default_out_dtype

__all__ = ["SKINNY_M_MAX", "skinny_ok", "sta_gemm_skinny_pallas",
           "dbb_gemm_skinny_pallas"]


def _epilogue_store(o_ref, acc_ref, bias_ref, scale_ref, epilogue, out_dtype):
    o_ref[...] = apply_epilogue(
        acc_ref[...], epilogue, out_dtype,
        bias=bias_ref[...] if bias_ref is not None else None,
        scale=scale_ref[...] if scale_ref is not None else None)


def _sta_skinny_kernel(x_ref, w_ref, *refs, n_k: int, block_k: int,
                       out_dtype, epilogue: Epilogue):
    """One (j, k) grid step: acc[j] += x[:, k-tile] @ w[k, j]; the x ref is
    the whole resident [M, K] block, sliced per K step."""
    refs = list(refs)
    bias_ref = refs.pop(0) if epilogue.has_bias else None
    scale_ref = refs.pop(0) if epilogue.has_scale else None
    o_ref, acc_ref = refs
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[:, pl.ds(k * block_k, block_k)]
    acc_ref[...] += jax.lax.dot_general(
        x, w_ref[...], dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(k == n_k - 1)
    def _store():
        _epilogue_store(o_ref, acc_ref, bias_ref, scale_ref, epilogue,
                        out_dtype)


def sta_gemm_skinny_pallas(
    x: jax.Array,             # [M, K] — fully resident
    w: jax.Array,             # [K, N] — streamed
    bias: Optional[jax.Array] = None,    # [1, N] f32
    scale: Optional[jax.Array] = None,   # [1, N] f32
    *,
    epilogue: Epilogue = Epilogue(),
    block_k: int = 128,
    block_n: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Dense skinny ``x @ w``: resident activations, streamed weights,
    fused epilogue in the final-K store."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % SUBLANE == 0 and m <= round_up(SKINNY_M_MAX, SUBLANE), m
    assert k % block_k == 0 and n % block_n == 0, (
        f"shape ({m},{k},{n}) not divisible by blocks "
        f"({block_k},{block_n}); pad at the ops layer")
    acc_dtype = acc_dtype_for(x.dtype)
    if out_dtype is None:
        out_dtype = default_out_dtype(x.dtype, epilogue)
    n_k = k // block_k

    operands = [x, w]
    in_specs = [
        pl.BlockSpec((m, k), lambda j, kk: (0, 0)),       # resident A
        pl.BlockSpec((block_k, block_n), lambda j, kk: (kk, j)),
    ]
    row_spec = pl.BlockSpec((1, block_n), lambda j, kk: (0, j))
    if epilogue.has_bias:
        assert bias is not None and bias.shape == (1, n), (
            "bias must be [1, N]", None if bias is None else bias.shape, n)
        operands.append(bias)
        in_specs.append(row_spec)
    if epilogue.has_scale:
        assert scale is not None and scale.shape == (1, n), (
            "scale must be [1, N]", None if scale is None else scale.shape, n)
        operands.append(scale)
        in_specs.append(row_spec)

    grid = (n // block_n, n_k)
    kernel = functools.partial(_sta_skinny_kernel, n_k=n_k, block_k=block_k,
                               out_dtype=out_dtype, epilogue=epilogue)
    return pl.pallas_call(
        kernel,
        name="sta_gemm_skinny",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, block_n), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((m, block_n), acc_dtype)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


def _dbb_skinny_kernel(x_ref, v_ref, m_ref, *refs, n_k: int, block_k: int,
                       block: int, nnz: int, out_dtype, epilogue: Epilogue,
                       bits: int = 8, group: int = 0):
    refs = list(refs)
    gs_ref = refs.pop(0) if bits == 4 else None
    bias_ref = refs.pop(0) if epilogue.has_bias else None
    scale_ref = refs.pop(0) if epilogue.has_scale else None
    o_ref, acc_ref = refs
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if bits == 4:
        gs = group_scale_rows(gs_ref, k, block_k=block_k, group=group)
        w = _dequant_tile(v_ref[...], m_ref[...], gs, block=block, nnz=nnz)
    else:
        w = _decompress_tile(v_ref[...], m_ref[...], block=block, nnz=nnz)
    x = x_ref[:, pl.ds(k * block_k, block_k)]
    acc_ref[...] += jax.lax.dot_general(
        x, w.astype(x.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(k == n_k - 1)
    def _store():
        _epilogue_store(o_ref, acc_ref, bias_ref, scale_ref, epilogue,
                        out_dtype)


def dbb_gemm_skinny_pallas(
    x: jax.Array,          # [M, K] — fully resident
    values: jax.Array,     # [K//B * k, N] compressed non-zeros (slot-major)
    bitmask: jax.Array,    # [K//B, N] int32
    bias: Optional[jax.Array] = None,    # [1, N] f32
    scale: Optional[jax.Array] = None,   # [1, N] f32
    *,
    epilogue: Epilogue = Epilogue(),
    block: int = 8,
    nnz: int = 4,
    block_k: int = 128,
    block_n: int = 128,
    out_dtype=None,
    interpret: bool = False,
    bits: int = 8,
    group: int = 0,
    gscale: Optional[jax.Array] = None,  # [K//G, N] f32 (bits=4 only)
) -> jax.Array:
    """Skinny ``x @ unpack(values, bitmask)``: resident activations, the
    COMPRESSED weight stream moves through the K loop and is decompressed in
    VMEM per tile — no dense [K, N] weight exists at any point. ``bits=4``
    streams the nibble-packed plane (37.5% of dense INT8 bytes) and
    dequantizes with ``gscale`` at the decompress step (DESIGN.md §16)."""
    m, k_dim = x.shape
    kc, n = values.shape
    nb_total = k_dim // block
    assert m % SUBLANE == 0 and m <= round_up(SKINNY_M_MAX, SUBLANE), m
    assert k_dim % block_k == 0 and block_k % block == 0
    assert n % block_n == 0

    acc_dtype = acc_dtype_for(x.dtype)
    if out_dtype is None:
        out_dtype = default_out_dtype(x.dtype, epilogue)
    n_k = k_dim // block_k
    nb_tile = block_k // block            # blocks per K tile
    bkc = nb_tile * nnz                   # compressed rows per K tile

    operands = [x, values, bitmask]
    if bits == 4:
        assert kc == nb_total * nnz // 2, (values.shape, k_dim, block, nnz)
        assert bkc % 2 == 0, (block_k, block, nnz)
        assert x.dtype != jnp.int8, "w4 dequantizes in VMEM: float x only"
        assert group > 0 and (block_k % group == 0 or group % block_k == 0)
        assert gscale is not None and gscale.shape == (k_dim // group, n)
        vals_spec = pl.BlockSpec((bkc // 2, block_n),
                                 lambda j, kk: (kk, j))
    else:
        assert kc == nb_total * nnz, (values.shape, k_dim, block, nnz)
        vals_spec = pl.BlockSpec((bkc, block_n), lambda j, kk: (kk, j))
    assert bitmask.shape == (nb_total, n), bitmask.shape
    in_specs = [
        pl.BlockSpec((m, k_dim), lambda j, kk: (0, 0)),   # resident A
        vals_spec,
        pl.BlockSpec((nb_tile, block_n), lambda j, kk: (kk, j)),
    ]
    if bits == 4:
        operands.append(gscale)        # resident [K/G, bn] scale column
        in_specs.append(pl.BlockSpec((k_dim // group, block_n),
                                     lambda j, kk: (0, j)))
    row_spec = pl.BlockSpec((1, block_n), lambda j, kk: (0, j))
    if epilogue.has_bias:
        assert bias is not None and bias.shape == (1, n), (
            "bias must be [1, N]", None if bias is None else bias.shape, n)
        operands.append(bias)
        in_specs.append(row_spec)
    if epilogue.has_scale:
        assert scale is not None and scale.shape == (1, n), (
            "scale must be [1, N]", None if scale is None else scale.shape, n)
        operands.append(scale)
        in_specs.append(row_spec)

    grid = (n // block_n, n_k)
    kernel = functools.partial(_dbb_skinny_kernel, n_k=n_k, block_k=block_k,
                               block=block, nnz=nnz, out_dtype=out_dtype,
                               epilogue=epilogue, bits=bits, group=group)
    return pl.pallas_call(
        kernel,
        name="dbb_gemm_skinny",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, block_n), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((m, block_n), acc_dtype)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
