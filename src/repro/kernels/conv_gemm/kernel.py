"""Implicit-GEMM convolution Pallas kernels (fused im2col in-kernel).

The paper lowers every conv layer to GEMM via im2col, and `models/cnn.py`
used to do that literally: materialize the patch matrix
``[B·Ho·Wo, kh·kw·C]`` in HBM (a kh·kw× activation blowup — 9× for 3×3)
and feed it to `sta_gemm`/`dbb_gemm`. Hardware im2col units (SPOTS,
arXiv:2107.13386) build the patch stream *inside* the systolic pipeline
instead; this kernel is the TPU analogue: the K-loop of the GEMM gathers
the ``(kh, kw, C)`` patch tile directly from the NHWC activation block in
VMEM, so the im2col tensor never exists in HBM (DESIGN.md §8).

Decomposition (DESIGN.md §8):

    out[b, oh, ow, n] = Σ_{i,j,c} x_pad[b, oh·s+i, ow·s+j, c] · w[(i·kw+j)·C+c, n]

    grid = (B, Ho/th, N/bn, kh)       th output rows per M tile, bm = th·Wo
    K step i (one kernel ROW offset, kw·C contraction columns):
      slab  = x[0, i + t0·s : i + t0·s + (th-1)·s + 1 : s, :, :]   # th rows
      for j < kw:                                 # patch column block j
        acc += slab[:, j : j+(Wo-1)·s+1 : s, :].reshape(th·Wo, C)
               @ w_tile[j·C : (j+1)·C]                              # MXU dot

The patch gather is a dynamic-start row slice plus kw static shifted
column slices of the VMEM-resident image block — no HBM gather, no
scatter. K ordering matches `conv_gemm.ref.im2col` exactly: spatial-major
(i·kw+j), channel-minor, so the weight matrix is the same ``[kh·kw·C, N]``
layout the explicit-im2col path consumes, and DBB 8×1 blocks run along it.
A K tile covers whole DBB blocks whenever ``(kw·C) % B == 0`` (the ops
layer enforces this for the packed variant).

The whole padded image ``[Hp, Wp, C]`` rides in VMEM as one block (mobile
CNN images are small: 32·32·512·4B = 2 MiB); the accumulator tile is
output-stationary scratch across the kh K steps, identical to the dense
STA kernel, and the shared `Epilogue` (bias/act/requant) runs on the final
K store.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import CompilerParams, acc_dtype_for, pltpu
from repro.kernels.dbb_gemm.kernel import _decompress_tile
from repro.kernels.epilogue import Epilogue, apply_epilogue, default_out_dtype

__all__ = ["conv_gemm_pallas", "conv_gemm_dbb_pallas"]


def _patch_slabs(x_ref, *, th: int, wo: int, kw: int, stride: int):
    """In-kernel im2col of one M×K tile, as kw ``[th·wo, C]`` slabs: slab
    ``j`` holds the patch rows' contraction columns ``j·C .. (j+1)·C-1``
    for the current (image-row tile, kernel-row offset) grid step.

    x_ref block is the whole padded image [1, Hp, Wp, C]; the row slab is a
    dynamic-start slice (start depends on grid ids) and the kw column
    shifts are static slices — of the loaded slab at stride 1, strided
    reads of the ref otherwise (Mosaic: 32-bit images only). The slabs are
    never stacked into one [th·wo, kw·C] patch: that rank-changing stack
    is a shape cast the TPU compiler refuses, so each slab meets its own
    rows of the weight tile instead (`_accumulate`)."""
    ih = pl.program_id(1)                  # output-row tile index
    ki = pl.program_id(3)                  # kernel row offset i ∈ [0, kh)
    r0 = ih * (th * stride) + ki
    c = x_ref.shape[-1]
    if stride == 1:
        slab = x_ref[0, pl.ds(r0, th)]     # [th, Wp, C]
        return [slab[:, j:j + wo, :].reshape(th * wo, c) for j in range(kw)]
    # strided reads straight from the ref (a strided slice of a loaded
    # value is a gather the TPU compiler refuses)
    return [x_ref[0, pl.ds(r0, th, stride=stride),
                  pl.ds(j, wo, stride=stride), :].reshape(th * wo, c)
            for j in range(kw)]


def _accumulate(acc_ref, slabs, w):
    """acc += patch @ w, one kw slab at a time: K order is j-major,
    c-minor, so slab j meets weight rows ``j·C .. (j+1)·C-1``."""
    c = slabs[0].shape[-1]
    acc = acc_ref[...]
    for j, slab in enumerate(slabs):
        acc += jax.lax.dot_general(
            slab, w[j * c:(j + 1) * c],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=acc_ref.dtype)
    acc_ref[...] = acc


def _store_epilogue(o_ref, acc_ref, bias_ref, scale_ref, *, epilogue,
                    out_dtype, th: int, wo: int):
    y = apply_epilogue(
        acc_ref[...], epilogue, out_dtype,
        bias=bias_ref[...] if bias_ref is not None else None,
        scale=scale_ref[...] if scale_ref is not None else None)
    o_ref[...] = y.reshape(1, th, wo, y.shape[-1])


def _conv_gemm_kernel(x_ref, w_ref, *refs, kh: int, kw: int, stride: int,
                      th: int, wo: int, out_dtype, epilogue: Epilogue):
    refs = list(refs)
    bias_ref = refs.pop(0) if epilogue.has_bias else None
    scale_ref = refs.pop(0) if epilogue.has_scale else None
    o_ref, acc_ref = refs
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    slabs = _patch_slabs(x_ref, th=th, wo=wo, kw=kw, stride=stride)
    _accumulate(acc_ref, slabs, w_ref[...])

    @pl.when(ki == kh - 1)
    def _store():
        _store_epilogue(o_ref, acc_ref, bias_ref, scale_ref,
                        epilogue=epilogue, out_dtype=out_dtype, th=th, wo=wo)


def _conv_gemm_dbb_kernel(x_ref, v_ref, m_ref, *refs, kh: int, kw: int,
                          stride: int, th: int, wo: int, block: int, nnz: int,
                          out_dtype, epilogue: Epilogue):
    """DBB variant: the weight K tile arrives compressed (values + bitmask)
    and is expanded in VMEM right before the dot — identical decompression
    to the dbb_gemm kernel, so the weight stream stays at the packed 62.5%
    of dense bytes end-to-end (cf. S2TA, arXiv:2107.07983)."""
    refs = list(refs)
    bias_ref = refs.pop(0) if epilogue.has_bias else None
    scale_ref = refs.pop(0) if epilogue.has_scale else None
    o_ref, acc_ref = refs
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    slabs = _patch_slabs(x_ref, th=th, wo=wo, kw=kw, stride=stride)
    w = _decompress_tile(v_ref[...], m_ref[...], block=block, nnz=nnz)
    _accumulate(acc_ref, slabs, w.astype(slabs[0].dtype))

    @pl.when(ki == kh - 1)
    def _store():
        _store_epilogue(o_ref, acc_ref, bias_ref, scale_ref,
                        epilogue=epilogue, out_dtype=out_dtype, th=th, wo=wo)


def _conv_specs(b: int, hp: int, wp: int, c: int, hot: int, wo: int,
                np_: int, th: int, bn: int, kh: int, epilogue: Epilogue,
                bias, scale):
    """Shared grid/spec plumbing for both variants (x, out, bias, scale)."""
    grid = (b, hot // th, np_ // bn, kh)
    x_spec = pl.BlockSpec((1, hp, wp, c), lambda bb, ih, jn, ki: (bb, 0, 0, 0))
    out_spec = pl.BlockSpec((1, th, wo, bn),
                            lambda bb, ih, jn, ki: (bb, ih, 0, jn))
    row_spec = pl.BlockSpec((1, bn), lambda bb, ih, jn, ki: (0, jn))
    extra_ops, extra_specs = [], []
    if epilogue.has_bias:
        assert bias is not None and bias.shape == (1, np_), (
            "bias must be [1, N]", None if bias is None else bias.shape, np_)
        extra_ops.append(bias)
        extra_specs.append(row_spec)
    if epilogue.has_scale:
        assert scale is not None and scale.shape == (1, np_), (
            "scale must be [1, N]", None if scale is None else scale.shape,
            np_)
        extra_ops.append(scale)
        extra_specs.append(row_spec)
    return grid, x_spec, out_spec, extra_ops, extra_specs


def conv_gemm_pallas(
    x: jax.Array,              # [B, Hp, Wp, C] spatially pre-padded NHWC
    w: jax.Array,              # [kh*kw*C, N] spatial-major, channel-minor
    bias: Optional[jax.Array] = None,    # [1, N] f32
    scale: Optional[jax.Array] = None,   # [1, N] f32
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    th: int,                   # output rows per M tile (bm = th * Wo)
    block_n: int = 128,
    epilogue: Epilogue = Epilogue(),
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Implicit-GEMM conv: returns [B, Hot, Wo, N] where Hot = the padded
    output-row count implied by Hp (the ops layer slices back to Ho).

    Contract: x is already padded so that Hp = (Hot-1)·stride + kh and
    Wp = (Wo-1)·stride + kw; N % block_n == 0; Hot % th == 0.
    """
    b, hp, wp, c = x.shape
    kdim, n = w.shape
    assert kdim == kh * kw * c, (w.shape, kh, kw, c)
    assert (hp - kh) % stride == 0 and (wp - kw) % stride == 0, (
        "pad spatial dims at the ops layer", x.shape, kh, kw, stride)
    hot = (hp - kh) // stride + 1
    wo = (wp - kw) // stride + 1
    assert hot % th == 0, (hot, th)
    assert n % block_n == 0, (n, block_n)
    acc_dtype = acc_dtype_for(x.dtype)
    if out_dtype is None:
        out_dtype = default_out_dtype(x.dtype, epilogue)

    grid, x_spec, out_spec, extra_ops, extra_specs = _conv_specs(
        b, hp, wp, c, hot, wo, n, th, block_n, kh, epilogue, bias, scale)
    w_spec = pl.BlockSpec((kw * c, block_n), lambda bb, ih, jn, ki: (ki, jn))

    kernel = functools.partial(
        _conv_gemm_kernel, kh=kh, kw=kw, stride=stride, th=th, wo=wo,
        out_dtype=out_dtype, epilogue=epilogue)
    return pl.pallas_call(
        kernel,
        name="conv_gemm",
        grid=grid,
        in_specs=[x_spec, w_spec] + extra_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, hot, wo, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((th * wo, block_n), acc_dtype)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w, *extra_ops)


def conv_gemm_dbb_pallas(
    x: jax.Array,              # [B, Hp, Wp, C] spatially pre-padded NHWC
    values: jax.Array,         # [kh*kw*C/B * k, N] compressed (slot-major)
    bitmask: jax.Array,        # [kh*kw*C/B, N] int32
    bias: Optional[jax.Array] = None,    # [1, N] f32
    scale: Optional[jax.Array] = None,   # [1, N] f32
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    th: int,
    block: int = 8,
    nnz: int = 4,
    block_n: int = 128,
    epilogue: Epilogue = Epilogue(),
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Implicit-GEMM conv against a DBB-compressed weight stream.

    Same contract as `conv_gemm_pallas` plus the DBB block geometry: the
    per-K-step contraction span is kw·C rows, which must cover whole DBB
    blocks — (kw·C) % block == 0 (the ops layer guards this).
    """
    b, hp, wp, c = x.shape
    kdim = kh * kw * c
    kc, n = values.shape
    nb_total = kdim // block
    assert kdim % block == 0 and (kw * c) % block == 0, (
        "K tile must cover whole DBB blocks", kh, kw, c, block)
    assert kc == nb_total * nnz, (values.shape, kdim, block, nnz)
    assert bitmask.shape == (nb_total, n), bitmask.shape
    assert (hp - kh) % stride == 0 and (wp - kw) % stride == 0, (
        "pad spatial dims at the ops layer", x.shape, kh, kw, stride)
    hot = (hp - kh) // stride + 1
    wo = (wp - kw) // stride + 1
    assert hot % th == 0, (hot, th)
    assert n % block_n == 0, (n, block_n)
    acc_dtype = acc_dtype_for(x.dtype)
    if out_dtype is None:
        out_dtype = default_out_dtype(x.dtype, epilogue)

    nb_step = (kw * c) // block            # DBB blocks per K step
    grid, x_spec, out_spec, extra_ops, extra_specs = _conv_specs(
        b, hp, wp, c, hot, wo, n, th, block_n, kh, epilogue, bias, scale)
    v_spec = pl.BlockSpec((nb_step * nnz, block_n),
                          lambda bb, ih, jn, ki: (ki, jn))
    m_spec = pl.BlockSpec((nb_step, block_n),
                          lambda bb, ih, jn, ki: (ki, jn))

    kernel = functools.partial(
        _conv_gemm_dbb_kernel, kh=kh, kw=kw, stride=stride, th=th, wo=wo,
        block=block, nnz=nnz, out_dtype=out_dtype, epilogue=epilogue)
    return pl.pallas_call(
        kernel,
        name="conv_gemm_dbb",
        grid=grid,
        in_specs=[x_spec, v_spec, m_spec] + extra_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, hot, wo, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((th * wo, block_n), acc_dtype)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, values, bitmask, *extra_ops)
