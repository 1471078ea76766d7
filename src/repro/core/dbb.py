"""Density-Bound Block (DBB) structured-sparse weight format (paper §IV-A).

A weight matrix ``W[K, N]`` (contraction dim first, as used by ``x @ W``) is
split into ``B×1`` blocks along K. DBB bounds the non-zeros per block:
``NNZ <= k``. Unlike block sparsity (all-or-nothing blocks), only the *count*
is constrained — the positions are free, which is why accuracy holds
(paper Table I) while hardware utilization is guaranteed a-priori.

Storage format (paper: "simple bitmask compression"; DESIGN.md §2):
  values  [K//B * k, N]  the (up to) k surviving values per block, slot-major
                         (row kb*k + s holds slot s of block kb), index-
                         sorted, zero-padded when a block has fewer than k
                         non-zeros
  bitmask [K//B, N]      uint32, bit ``pos`` set ⇔ dense row kb*B + pos kept
                         — what the Pallas kernels and `decompress_ref`
                         consume (rank(pos) = popcount of the lower bits
                         recovers the slot)
  indices [K//B * k, N]  block-local positions (0..B-1) of each value, int32
                         — diagnostics/validation only; the serving format
                         drops them (4 B/value vs the 1 mask byte per block)

For B=8, k=4, INT8: (4 value bytes + 1 mask byte) / 8 bytes = 62.5% of dense
⇒ the paper's 37.5% weight-memory reduction.

Sub-8-bit values plane (DESIGN.md §16): ``pack_dbb(..., bits=4, group=G)``
stores the surviving values as nibble-packed INT4 — two slots per int8
byte (packed row i holds compressed row 2i in the low nibble, 2i+1 in the
high nibble) — quantized symmetrically to [-7, 7] per group of G dense K
rows, with the per-group scales in ``scale [K//G, N]`` f32. The group must
be a multiple of the DBB block so a compressed row's scale group is
column-independent (every dense position of block kb lands in group
kb·B // G). For B=8, k=4, INT4: (2 value bytes + 1 mask byte) / 8 = 37.5%
of dense INT8 bytes — the decode weight stream roughly halves again.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "DbbWeight", "dbb_mask", "dbb_project", "pack_dbb", "unpack_dbb",
    "pack_nibbles", "unpack_nibbles", "INT4_MAX",
    "dbb_footprint_bytes", "dense_footprint_bytes", "validate_dbb",
]

# symmetric INT4 grid [-7, 7] (the -8 code is unused, like INT8's -128)
INT4_MAX = 7


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DbbWeight:
    """Packed DBB weight. A pytree; `block`/`nnz`/`k_dim`/`bits`/`group`
    are static. ``bits=8`` stores one value per ``values`` element;
    ``bits=4`` nibble-packs two INT4 slots per int8 byte and ``scale``
    holds the groupwise ``[K//G, N]`` dequant plane (DESIGN.md §16)."""
    values: jax.Array    # [K//B * k, N]  (bits=4: [K//B * k // 2, N] int8)
    indices: jax.Array   # [K//B * k, N] int32, block-local in [0, B)
    bitmask: jax.Array   # [K//B, N] uint32
    scale: Optional[jax.Array]  # [N] per-channel (bits=8) / [K//G, N] (bits=4)
    block: int = dataclasses.field(metadata=dict(static=True), default=8)
    nnz: int = dataclasses.field(metadata=dict(static=True), default=4)
    k_dim: int = dataclasses.field(metadata=dict(static=True), default=0)
    bits: int = dataclasses.field(metadata=dict(static=True), default=8)
    group: int = dataclasses.field(metadata=dict(static=True), default=0)

    @property
    def n_dim(self) -> int:
        return self.values.shape[-1]

    @property
    def num_blocks(self) -> int:
        return self.k_dim // self.block


def _check_dims(k_dim: int, block: int, nnz: int) -> None:
    if k_dim % block != 0:
        raise ValueError(f"K={k_dim} not divisible by DBB block={block}")
    if not (1 <= nnz <= block):
        raise ValueError(f"nnz={nnz} must be in [1, block={block}]")


def _bitonic_kth_largest(mags: jax.Array, k: int) -> jax.Array:
    """k-th largest along axis 1 (size B, power of two) via a Batcher
    bitonic network of elementwise min/max pairs.

    Why not lax.top_k: it lowers to a variadic sort that the SPMD
    partitioner refuses to keep sharded on the non-sorted dims, so the DBB
    projection all-gathered the weights' model axis every step
    (§Perf iteration 11). Compare-exchanges are plain elementwise ops —
    fully partitionable.
    """
    b = mags.shape[1]
    lanes = [mags[:, i] for i in range(b)]

    def networks(n):
        # Batcher odd-even mergesort compare-exchange schedule
        out = []
        p = 1
        while p < n:
            kk = p
            while kk >= 1:
                for j in range(kk % p, n - kk, 2 * kk):
                    for i in range(0, min(kk, n - j - kk)):
                        if (i + j) // (2 * p) == (i + j + kk) // (2 * p):
                            out.append((i + j, i + j + kk))
                kk //= 2
            p *= 2
        return out

    for a, c in networks(b):      # ascending: lane b-k holds k-th largest
        lo = jnp.minimum(lanes[a], lanes[c])
        hi = jnp.maximum(lanes[a], lanes[c])
        lanes[a], lanes[c] = lo, hi
    return lanes[b - k]


def dbb_mask(w: jax.Array, block: int, nnz: int) -> jax.Array:
    """Boolean keep-mask: top-|w| `nnz` entries of every B-block along axis 0.

    Ties are broken toward lower indices (deterministic), matching
    amplitude-based pruning in the paper §V-A.
    """
    k_dim, n = w.shape
    _check_dims(k_dim, block, nnz)
    if nnz == block:
        return jnp.ones_like(w, dtype=bool)
    blocks = jnp.abs(w.reshape(k_dim // block, block, n))    # [Kb, B, N]
    if block & (block - 1) == 0:
        thr = _bitonic_kth_largest(blocks, nnz)[:, None, :]  # [Kb, 1, N]
        gt = blocks > thr
        # fill remaining slots from the == thr ties, lowest index first
        need = nnz - gt.sum(axis=1, keepdims=True)
        eq = blocks == thr
        rank = jnp.cumsum(eq, axis=1)
        keep = gt | (eq & (rank <= need))
        return keep.reshape(k_dim, n)
    # non-power-of-two block: top_k fallback
    bt = blocks.transpose(0, 2, 1)                           # [Kb, N, B]
    _, idx = jax.lax.top_k(bt, nnz)
    keep = jnp.put_along_axis(jnp.zeros(bt.shape, bool), idx, True,
                              axis=-1, inplace=False)
    return keep.transpose(0, 2, 1).reshape(k_dim, n)


def dbb_project(w: jax.Array, block: int, nnz: int) -> jax.Array:
    """Project a dense matrix onto the DBB constraint set (zero the rest)."""
    return jnp.where(dbb_mask(w, block, nnz), w, jnp.zeros_like(w))


def pack_nibbles(q: jax.Array) -> jax.Array:
    """Nibble-pack an int8 array of INT4-range rows: ``[R, N] → [R//2, N]``,
    packed row i = row 2i in the low nibble, row 2i+1 in the high nibble.
    R must be even; values must lie in [-8, 7]."""
    r, _ = q.shape
    if r % 2 != 0:
        raise ValueError(f"nibble packing needs an even row count, got {r}")
    u = jax.lax.bitcast_convert_type(q.astype(jnp.int8), jnp.uint8)
    lo = u[0::2] & 0xF
    hi = u[1::2] & 0xF
    return jax.lax.bitcast_convert_type(lo | (hi << 4), jnp.int8)


def unpack_nibbles(packed: jax.Array) -> jax.Array:
    """Inverse of `pack_nibbles`: ``[R//2, N] int8 → [R, N] int8`` with each
    nibble sign-extended. Pure shift arithmetic (``(p << 4) >> 4`` for the
    low nibble, ``p >> 4`` for the high one) so the same expansion runs
    unchanged inside the Pallas kernel bodies."""
    r2, n = packed.shape
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    hi = jnp.right_shift(packed, 4)
    return jnp.stack([lo, hi], axis=1).reshape(r2 * 2, n)


def _check_w4_dims(k_dim: int, block: int, nnz: int, group: int) -> None:
    if group <= 0 or group % block != 0:
        raise ValueError(f"group={group} must be a positive multiple of "
                         f"block={block} (scale groups cover whole blocks)")
    if k_dim % group != 0:
        raise ValueError(f"K={k_dim} not divisible by group={group}")
    if (k_dim // block * nnz) % 2 != 0:
        raise ValueError(
            f"K//B·k = {k_dim // block * nnz} compressed rows must be even "
            f"to nibble-pack (K={k_dim}, block={block}, nnz={nnz})")


def pack_dbb(
    w: jax.Array, block: int = 8, nnz: int = 4,
    scale: Optional[jax.Array] = None,
    bits: int = 8, group: int = 128,
) -> DbbWeight:
    """Compress ``W[K, N]`` to the DBB format (projects first if needed).

    Returns a `DbbWeight` with ``values [K/B·k, N]`` (slot-major),
    ``bitmask [K/B, N]`` and diagnostic ``indices [K/B·k, N]`` — the layout
    contract in DESIGN.md §2, shared with `kernels.dbb_gemm`. K must divide
    by ``block``; N is unconstrained here (kernels pad it).

    ``bits=4`` additionally quantizes the surviving values to the
    symmetric INT4 grid per ``group`` dense K rows (group % block == 0,
    K % group == 0), nibble-packs the values plane to ``[K/B·k/2, N]`` and
    stores the per-group scales in ``scale [K//G, N]`` (DESIGN.md §16);
    a caller-supplied ``scale`` is not accepted in that mode.
    """
    if bits not in (4, 8):
        raise ValueError(f"bits={bits} not supported (4 or 8)")
    if bits == 4:
        if scale is not None:
            raise ValueError("bits=4 derives groupwise scales itself; "
                             "per-channel scale is the bits=8 format")
        return _pack_dbb_w4(w, block, nnz, group)
    k_dim, n = w.shape
    _check_dims(k_dim, block, nnz)
    kb = k_dim // block
    # everything stays [Kb, B, N] with N on the lanes: a [.., N, k] layout
    # would pad its k-wide minor dim to a full 128-lane tile on TPU (32x
    # the bytes at k = 4), which does not fit HBM for a stacked layer
    blocks = w.reshape(kb, block, n)                          # [Kb, B, N]
    sel = dbb_mask(w, block, nnz).reshape(kb, block, n)       # top-k, ties low
    # canonical slot order = bitmask-rank order: live (non-zero) values
    # compact into the leading slots in index order, selected zeros
    # (dead slots) trail — what the kernels' popcount-rank decompression
    # assumes. Continuous weights never produce dead slots mid-block, but
    # quantized (bits=4) input routinely rounds selected values to zero.
    live = sel & (blocks != 0)
    dead = sel & ~live
    n_live = live.sum(axis=1, keepdims=True, dtype=jnp.int8)
    slot = jnp.where(live, jnp.cumsum(live, axis=1, dtype=jnp.int8),
                     n_live + jnp.cumsum(dead, axis=1, dtype=jnp.int8)) - 1
    pos = jnp.arange(block, dtype=jnp.int32)[None, :, None]
    zero = jnp.zeros((), blocks.dtype)
    vals, idxs = [], []
    for s in range(nnz):           # one selected position per (block, col)
        hit = sel & (slot == s)
        vals.append(jnp.where(hit & live, blocks, zero)
                    .sum(axis=1, dtype=blocks.dtype))
        idxs.append(jnp.where(hit, pos, 0).sum(axis=1, dtype=jnp.int32))
    values = jnp.stack(vals, axis=1).reshape(kb * nnz, n)
    indices = jnp.stack(idxs, axis=1).reshape(kb * nnz, n)
    bitmask = jnp.where(live, jnp.uint32(1) << pos.astype(jnp.uint32),
                        jnp.uint32(0)).sum(axis=1, dtype=jnp.uint32)
    return DbbWeight(values=values, indices=indices, bitmask=bitmask,
                     scale=scale, block=block, nnz=nnz, k_dim=k_dim)


def _pack_dbb_w4(w: jax.Array, block: int, nnz: int,
                 group: int) -> DbbWeight:
    """bits=4 pack: groupwise symmetric quantize to [-7, 7], DBB-select on
    the *quantized* grid (so the bitmask matches the stored INT4 values
    exactly), then nibble-pack the values plane."""
    k_dim, n = w.shape
    _check_dims(k_dim, block, nnz)
    _check_w4_dims(k_dim, block, nnz, group)
    g = w.astype(jnp.float32).reshape(k_dim // group, group, n)
    scale = (jnp.max(jnp.abs(g), axis=1) / INT4_MAX).astype(jnp.float32)
    scale = jnp.where(scale > 0, scale, jnp.ones_like(scale))  # [K//G, N]
    q = jnp.clip(jnp.round(g / scale[:, None, :]), -INT4_MAX, INT4_MAX)
    q = q.reshape(k_dim, n).astype(jnp.int8)
    p8 = pack_dbb(q, block=block, nnz=nnz)    # top-k on the INT4 grid
    return DbbWeight(values=pack_nibbles(p8.values), indices=p8.indices,
                     bitmask=p8.bitmask, scale=scale, block=block,
                     nnz=nnz, k_dim=k_dim, bits=4, group=group)


def _decompress_bitmask(values: jax.Array, bitmask: jax.Array, *,
                        block: int) -> jax.Array:
    """Bitmask-rank decompression ``[Kb·k, N] + [Kb, N] → [K, N]`` — the
    indices-free analogue of `unpack_dbb`'s one-hot path, for leaves whose
    diagnostic ``indices`` plane has been stripped (the serving format).
    Same rank = popcount-of-lower-bits recovery the kernels use."""
    kbn, n = values.shape
    kb = bitmask.shape[0]
    k = kbn // kb
    vals = values.reshape(kb, k, n)
    pos = jnp.arange(block, dtype=jnp.uint32)
    bits = ((bitmask[:, None, :] >> pos[None, :, None]) & 1)  # [Kb, B, N]
    rank = (jnp.cumsum(bits, axis=1) - bits).astype(jnp.int32)
    rank = jnp.clip(rank, 0, k - 1)
    gathered = jnp.take_along_axis(vals, rank, axis=1)        # [Kb, B, N]
    dense = jnp.where(bits.astype(bool), gathered,
                      jnp.zeros_like(gathered))
    return dense.reshape(kb * block, n)


def unpack_dbb(p: DbbWeight) -> jax.Array:
    """Decompress a `DbbWeight` to dense ``[K, N]`` and apply the scale
    plane if present — the host-side analogue of the kernels' in-VMEM
    decompression (DESIGN.md §2). ``bits=4`` leaves sign-extend the
    nibble plane first and dequantize with the groupwise ``[K//G, N]``
    scales; leaves whose diagnostic ``indices`` were stripped (serving)
    fall back to bitmask-rank decompression."""
    kb, n, k = p.num_blocks, p.n_dim, p.nnz
    values = unpack_nibbles(p.values) if p.bits == 4 else p.values
    if p.indices is None:
        out = _decompress_bitmask(values, p.bitmask, block=p.block)
    else:
        vals = values.reshape(kb, k, n).transpose(0, 2, 1)    # [Kb, N, k]
        idx = p.indices.reshape(kb, k, n).transpose(0, 2, 1)  # [Kb, N, k]
        onehot = jax.nn.one_hot(idx, p.block, dtype=vals.dtype, axis=-1)
        dense = jnp.einsum("bnk,bnkB->bnB", vals, onehot)     # [Kb, N, B]
        out = dense.transpose(0, 2, 1).reshape(p.k_dim, n)
    if p.bits == 4:
        grouped = out.astype(jnp.float32).reshape(
            p.k_dim // p.group, p.group, n)
        return (grouped * p.scale[:, None, :]).reshape(p.k_dim, n)
    if p.scale is not None:
        out = out * p.scale[None, :]
    return out


def dense_footprint_bytes(k_dim: int, n: int, itemsize: int = 1) -> int:
    return k_dim * n * itemsize


def dbb_footprint_bytes(k_dim: int, n: int, block: int, nnz: int,
                        itemsize: int = 1, bits: int = 8,
                        group: int = 0) -> int:
    """Compressed bytes: values + per-block bitmask (paper §IV-A).
    ``bits=4`` halves the values plane (nibble packing) and adds the
    groupwise f32 scale plane ``[K//G, N]`` (DESIGN.md §16)."""
    kb = k_dim // block
    mask_bytes = (block + 7) // 8
    if bits == 4:
        val_bytes = (kb * nnz + 1) // 2 * n       # two slots per byte
        scale_bytes = (k_dim // group) * n * 4 if group > 0 else 0
        return val_bytes + kb * n * mask_bytes + scale_bytes
    return kb * n * (nnz * itemsize + mask_bytes)


def validate_dbb(p: DbbWeight) -> Tuple[bool, str]:
    """Host-side invariant check (used by tests & checkpoint loading)."""
    if p.indices is None:
        return False, "indices plane stripped (serving format); " \
                      "validate against the host-side copy"
    values = unpack_nibbles(p.values) if p.bits == 4 else p.values
    vals = np.asarray(values).reshape(p.num_blocks, p.nnz, p.n_dim)
    idx = np.asarray(p.indices).reshape(p.num_blocks, p.nnz, p.n_dim)
    if idx.min() < 0 or idx.max() >= p.block:
        return False, f"index out of range [0,{p.block})"
    # indices strictly increasing wherever two non-zero values share a block
    nz = np.abs(vals) > 0
    for b in range(min(p.num_blocks, 64)):   # bounded spot-check
        for col in range(min(p.n_dim, 64)):
            live = idx[b, nz[b, :, col], col]
            if live.size and np.any(np.diff(live) < 0):
                return False, f"indices not sorted in block {b} col {col}"
    per_block_nnz = nz.sum(axis=1)
    if per_block_nnz.max(initial=0) > p.nnz:
        return False, "NNZ bound violated"
    return True, "ok"
