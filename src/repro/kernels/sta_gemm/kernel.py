"""STA dense GEMM Pallas kernel — the Tensor-PE array as VMEM tiling.

Paper mapping (DESIGN.md §2): the A×B×C @ M×N tensor-PE grid becomes a
(bm, bk, bn) block decomposition. The accumulator tile is *output-stationary*
in VMEM scratch across the K grid dimension — the TPU analogue of keeping
INT32 accumulators in place while INT8 operands shift through the array
(the paper's modified dataflow, §II). INT8 operands accumulate in INT32 via
``preferred_element_type``, exactly the SA/STA datapath.

Fused epilogue (DESIGN.md §7): on the final K step the optional
bias/activation/requant epilogue runs on the accumulator tile *in VMEM*
before the single store — the output never round-trips through HBM in its
pre-activation form. Bias and scale ride along as [1, N] operands blocked
to [1, bn] per output column tile.

Shape contract:
    x [M, K] · w [K, N] → out [M, N]
    bias, scale (optional): [1, N] f32, broadcast over rows.
    M % block_m == K % block_k == N % block_n == 0 (pad at the ops layer).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import CompilerParams, acc_dtype_for, pltpu
from repro.kernels.epilogue import Epilogue, apply_epilogue, default_out_dtype

__all__ = ["sta_gemm_pallas"]


def _sta_gemm_kernel(x_ref, w_ref, *refs, n_k: int, out_dtype,
                     epilogue: Epilogue):
    """One (i, j, k) grid step: acc[i,j] += x[i,k] @ w[k,j]; epilogue+store
    on the last k."""
    refs = list(refs)
    bias_ref = refs.pop(0) if epilogue.has_bias else None
    scale_ref = refs.pop(0) if epilogue.has_scale else None
    o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        x, w, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = apply_epilogue(
            acc_ref[...], epilogue, out_dtype,
            bias=bias_ref[...] if bias_ref is not None else None,
            scale=scale_ref[...] if scale_ref is not None else None)


def sta_gemm_pallas(
    x: jax.Array,             # [M, K]
    w: jax.Array,             # [K, N]
    bias: Optional[jax.Array] = None,    # [1, N] f32
    scale: Optional[jax.Array] = None,   # [1, N] f32
    *,
    epilogue: Epilogue = Epilogue(),
    block_m: int = 128,
    block_k: int = 128,
    block_n: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Dense ``x @ w`` with output-stationary VMEM accumulation and an
    optional fused bias/activation/requant epilogue in the final-K store."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0, (
        f"shape ({m},{k},{n}) not divisible by blocks "
        f"({block_m},{block_k},{block_n}); pad at the ops layer")
    acc_dtype = acc_dtype_for(x.dtype)
    if out_dtype is None:
        out_dtype = default_out_dtype(x.dtype, epilogue)
    n_k = k // block_k

    operands = [x, w]
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
    ]
    row_spec = pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j))
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

    grid = (m // block_m, n // block_n, n_k)
    kernel = functools.partial(_sta_gemm_kernel, n_k=n_k, out_dtype=out_dtype,
                               epilogue=epilogue)
    return pl.pallas_call(
        kernel,
        name="sta_gemm_tiled",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), acc_dtype)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
