"""DBB structured-sparse GEMM Pallas kernel (paper §IV, STA-DBB).

TPU adaptation (DESIGN.md §2): the STA-DBB hardware feeds each dot unit the
``k`` non-zero weights plus a bitmask, and *muxes* the matching activations.
The MXU has no muxes, so the exploitable win on TPU is **HBM bandwidth**: the
weight stream stays DBB-compressed in HBM — `values [K/B·k, N]` + one mask
byte per block, 62.5% of dense bytes at k=4/B=8 — and is decompressed
*inside the kernel* in VMEM right before the MXU dot. Decode-time GEMMs are
memory-bound, so the compression moves the dominant roofline term directly.

The decompression is the paper's mux, inverted: for dense block position
``pos``, the source slot is ``rank(pos) = popcount(mask & ((1<<pos)-1))`` and
the value is kept iff bit ``pos`` is set. Everything is unrolled over the
static block geometry (B, k), so the kernel body is pure VPU select/add ops
followed by a single MXU dot per tile.

Accumulation is output-stationary in VMEM scratch across the K grid
dimension, identical to the dense STA kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import CompilerParams, acc_dtype_for, pltpu, popcount_u32
from repro.kernels.epilogue import Epilogue, apply_epilogue, default_out_dtype

__all__ = ["dbb_gemm_pallas"]


def _decompress_tile(vals, mask, *, block: int, nnz: int):
    """Expand a compressed weight tile to dense.

    vals: [nb * nnz, bn]  (slot-major per block: rows kb*nnz + s)
    mask: [nb, bn] int32 bitmask, bit pos set ⇔ dense position kept
    returns: [nb * block, bn] dense tile
    """
    nb_nnz, bn = vals.shape
    nb = nb_nnz // nnz
    v = vals.reshape(nb, nnz, bn)
    rows = []
    for pos in range(block):
        bit = (mask >> pos) & 1                        # [nb, bn]
        below = mask & ((1 << pos) - 1)
        rank = popcount_u32(below, pos) if pos else jnp.zeros_like(mask)
        val_at_rank = jnp.zeros_like(v[:, 0, :])
        for s in range(min(nnz, pos + 1)):
            val_at_rank = jnp.where(rank == s, v[:, s, :], val_at_rank)
        rows.append(jnp.where(bit == 1, val_at_rank,
                              jnp.zeros_like(val_at_rank)))
    dense = jnp.stack(rows, axis=1)                    # [nb, block, bn]
    return dense.reshape(nb * block, bn)


def _expand_nibbles(packed):
    """Sign-extend a nibble-packed int8 tile ``[r/2, bn] → [r, bn]`` int32:
    packed row i holds compressed row 2i (low nibble, ``(p << 28) >> 28``)
    and row 2i+1 (high nibble, ``p >> 4``) — pure VPU shift arithmetic,
    the in-kernel mirror of `core.dbb.unpack_nibbles`. The shifts run on
    int32 because the TPU VPU has no int8 shifts."""
    r2, bn = packed.shape
    p = packed.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(p, 28), 28)
    hi = jnp.right_shift(p, 4)
    return jnp.stack([lo, hi], axis=1).reshape(r2 * 2, bn)


def group_scale_rows(gs_ref, kk, *, block_k: int, group: int):
    """The ``[gpt, bn]`` groupwise scales covering K tile ``kk``, read
    from the grid-constant ``[K/G, bn]`` scale block (a block of one scale
    row per tile would be a second-minor dim of 1, which Mosaic refuses).
    When a group spans several K tiles, successive tiles reread its row."""
    gpt = max(block_k // group, 1)
    return gs_ref[pl.ds((kk * block_k) // group, gpt), :]


def _dequant_tile(vals, mask, gscale, *, block: int, nnz: int):
    """w4 decompress-tile step: expand the nibble plane, bitmask-rank
    decompress to the dense [bk, bn] tile, then dequantize with the
    per-group scales ``gscale [gpt, bn]`` (gpt groups cover the K tile).
    All in VMEM — neither the int8-expanded nor the dense weight ever
    exists in HBM."""
    w = _decompress_tile(_expand_nibbles(vals), mask, block=block, nnz=nnz)
    bk, bn = w.shape
    gpt = gscale.shape[0]
    w = w.astype(jnp.float32).reshape(gpt, bk // gpt, bn) * gscale[:, None, :]
    return w.reshape(bk, bn)


def _dbb_gemm_kernel(x_ref, v_ref, m_ref, *refs, n_k: int, block_k: int,
                     block: int, nnz: int, out_dtype, epilogue: Epilogue,
                     bits: int = 8, group: int = 0):
    refs = list(refs)
    gs_ref = refs.pop(0) if bits == 4 else None
    bias_ref = refs.pop(0) if epilogue.has_bias else None
    scale_ref = refs.pop(0) if epilogue.has_scale else None
    o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if bits == 4:
        gs = group_scale_rows(gs_ref, k, block_k=block_k, group=group)
        w = _dequant_tile(v_ref[...], m_ref[...], gs, block=block, nnz=nnz)
    else:
        w = _decompress_tile(v_ref[...], m_ref[...], block=block, nnz=nnz)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w.astype(x_ref.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = apply_epilogue(
            acc_ref[...], epilogue, out_dtype,
            bias=bias_ref[...] if bias_ref is not None else None,
            scale=scale_ref[...] if scale_ref is not None else None)


def dbb_gemm_pallas(
    x: jax.Array,          # [M, K]
    values: jax.Array,     # [K//B * k, N] compressed non-zeros (slot-major)
    bitmask: jax.Array,    # [K//B, N] int32 (low `block` bits used)
    bias: jax.Array = None,    # [1, N] f32 (epilogue.has_bias)
    scale: jax.Array = None,   # [1, N] f32 (epilogue.has_scale)
    *,
    epilogue: Epilogue = Epilogue(),
    block: int = 8,
    nnz: int = 4,
    block_m: int = 128,
    block_k: int = 128,
    block_n: int = 128,
    out_dtype=None,
    interpret: bool = False,
    bits: int = 8,
    group: int = 0,
    gscale: jax.Array = None,  # [K//G, N] f32 (bits=4 only)
) -> jax.Array:
    """``x @ unpack(values, bitmask)`` with on-chip DBB decompression and an
    optional fused bias/activation/requant epilogue in the final-K store.

    Shape contract (DESIGN.md §2): for dense contraction dim K and DBB
    geometry (B=block, k=nnz), the weight stream is
        values  [K/B · k, N]  slot-major (row kb·k + s = slot s of block kb)
        bitmask [K/B, N]      int32, bit ``pos`` set ⇔ dense row
                              kb·B + pos is kept
    K must divide by block_k and block_k by B, so every K tile covers whole
    DBB blocks.

    ``bits=4`` (DESIGN.md §16): ``values`` is the nibble-packed plane
    ``[K/B·k/2, N] int8`` and ``gscale [K//G, N]`` the groupwise dequant
    scales; the kernel streams the packed plane, sign-extends + dequantizes
    at the decompress-tile step, so neither the int8-expanded nor the dense
    weight ever exists in HBM. Requires float activations and block_k and
    group to nest (block_k % group == 0 or group % block_k == 0).
    """
    m, k_dim = x.shape
    kc, n = values.shape
    nb_total = k_dim // block
    assert k_dim % block_k == 0 and block_k % block == 0
    assert m % block_m == 0 and n % block_n == 0

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
                                 lambda i, j, kk: (kk, j))
    else:
        assert kc == nb_total * nnz, (values.shape, k_dim, block, nnz)
        vals_spec = pl.BlockSpec((bkc, block_n), lambda i, j, kk: (kk, j))
    assert bitmask.shape == (nb_total, n), bitmask.shape
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
        vals_spec,
        pl.BlockSpec((nb_tile, block_n), lambda i, j, kk: (kk, j)),
    ]
    if bits == 4:
        # the whole [K/G, bn] scale column stays resident across the K
        # loop; the kernel slices the rows of each K tile
        operands.append(gscale)
        in_specs.append(pl.BlockSpec((k_dim // group, block_n),
                                     lambda i, j, kk: (0, j)))
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
    kernel = functools.partial(_dbb_gemm_kernel, n_k=n_k, block_k=block_k,
                               block=block, nnz=nnz, out_dtype=out_dtype,
                               epilogue=epilogue, bits=bits, group=group)
    return pl.pallas_call(
        kernel,
        name="dbb_gemm_tiled",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), acc_dtype)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
