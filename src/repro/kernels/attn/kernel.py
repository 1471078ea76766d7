"""Flash-style fused attention Pallas kernels (DESIGN.md §10).

Attention is two chained GEMMs (QKᵀ and PV) around a softmax; the paper's
thesis — blocked operand reuse inside a tiled datapath (STA §III) — applies
to it exactly as to the MLP GEMMs. These kernels keep the whole
score→softmax→context chain on-chip:

* **prefill** (`flash_prefill_pallas`): blocks over the KV sequence with an
  *online softmax* — running (m, l, acc) statistics live in VMEM scratch
  across the KV grid dimension, so the ``[B, H, T, S]`` score tensor never
  exists in HBM (or anywhere: only one ``[block_q, block_kv]`` tile is ever
  live). Causal + sliding-window + left-pad masking uses the same
  qpos/kpos offset convention as ``models.attention._mask_bias``: logical
  positions are ``absolute - start[b]``, and since both q and k shift by
  the same per-row ``start``, the causal/window structure is invariant in
  absolute coordinates — only the pad mask (``kpos >= 0`` ⇔
  ``k_abs >= start[b]``) depends on it. Blocks entirely above the causal
  diagonal or entirely outside the window are skipped (`pl.when`).

* **decode** (`paged_decode_pallas`): M = GQA group size query rows
  (M ≤ 32 — the skinny regime, `kernels.common.skinny_ok`) stay resident
  while KV streams through the K loop in fixed-size **pages** gathered via
  a per-row **block table** (scalar-prefetched, so the table lookup drives
  the DMA index map — the physical page layout in HBM is arbitrary). The
  pool is the serving cache's whole [L, P, page, Hkv, D] stack and a
  scalar-prefetched layer index picks the layer, so a layer loop hands
  the kernel the pool it carries instead of a sliced-out layer. A
  contiguous cache is the special case of an identity block table over a
  one-layer stack, which is how `decode_attention_apply` reuses this
  kernel (DESIGN.md §10).

Numerics match the chunked XLA path in `models.attention`: scores
accumulate in f32 on the MXU (operands stay in storage dtype), the
optional logit softcap applies before masking, probabilities are cast to
the V storage dtype for the PV matmul with f32 accumulation, and the
final normalization divides by ``max(l, 1e-30)``.

Shape contract (pad at the ops layer):
    prefill: q [B, Hq, T, D], k/v [B, Hkv, S, D], start [B, 1] int32,
             T % block_q == 0, S % block_kv == 0, Hq % Hkv == 0
    decode:  q [B, Hkv, G, D], k/v pools [L, P, page, Hkv, D] stacked over
             layers (one grid step reads one page of every head of layer
             ``layer``), block table [B, n_log] int32, lengths/start [B]
             int32, layer [1] int32
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import CompilerParams, pltpu

__all__ = ["flash_prefill_pallas", "flash_prefill_packed_pallas",
           "paged_decode_pallas", "NEG_INF"]

NEG_INF = -1e30          # same sentinel as models.attention._mask_bias
_L_EPS = 1e-30           # matches the chunked path's combine guard


def _softcap(s: jax.Array, cap: float) -> jax.Array:
    return cap * jnp.tanh(s / cap) if cap > 0 else s


def _online_update(s, v, m_ref, l_ref, acc_ref):
    """One online-softmax step: fold the masked score tile ``s`` [M, Skv]
    and value tile ``v`` [Skv, D] into the running (m, l, acc) scratch."""
    m_prev = m_ref[:, :1]                               # [M, 1]
    m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)                     # [M, 1]
    p = jnp.exp(s - m_cur)                              # [M, Skv]
    l_cur = l_ref[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def _flash_prefill_kernel(start_ref, qoff_ref, q_ref, k_ref, v_ref, o_ref,
                          m_ref, l_ref, acc_ref, *, n_kv: int, block_q: int,
                          block_kv: int, sm_scale: float, window: int,
                          softcap: float, out_dtype):
    bb = pl.program_id(0)
    i = pl.program_id(2)
    j = pl.program_id(3)
    start = start_ref[bb]
    qoff = qoff_ref[bb]          # chunked-prefill continuation offset (§12)
    qi0 = qoff + i * block_q
    kj0 = j * block_kv

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block skip: any (qpos, kpos) pair alive ⇔ kj_min <= qi_max (causal,
    # start-invariant in absolute coordinates), kj_max inside the window,
    # and kj_max past the row's left padding (fully-pad blocks of a ragged
    # batch contribute nothing — the alpha washout would discard them)
    run = kj0 <= qi0 + block_q - 1
    run &= kj0 + block_kv - 1 >= start
    if window > 0:
        run &= kj0 + block_kv - 1 > qi0 - window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]                                 # [bq, D]
        k = k_ref[0, 0]                                 # [bkv, D]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = _softcap(s, softcap)
        qi = qi0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kj = kj0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (kj <= qi) & (kj >= start)
        if window > 0:
            mask &= kj > qi - window
        s = jnp.where(mask, s, NEG_INF)
        _online_update(s, v_ref[0, 0], m_ref, l_ref, acc_ref)

    @pl.when(j == n_kv - 1)
    def _store():
        l = jnp.maximum(l_ref[:, :1], _L_EPS)
        o_ref[0, 0] = (acc_ref[...] / l).astype(out_dtype)


def flash_prefill_pallas(
    q: jax.Array,                 # [B, Hq, T, D]
    k: jax.Array,                 # [B, Hkv, S, D]
    v: jax.Array,                 # [B, Hkv, S, D]
    start: Optional[jax.Array] = None,    # [B] int32, first real key slot
    q_offset: Optional[jax.Array] = None,  # [B] int32, abs pos of q row 0
    *,
    sm_scale: float,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Causal (+ sliding window, + left-pad) flash attention over a full
    sequence. Returns o [B, Hq, T, D] in q.dtype.

    q_offset [B] (optional): absolute key-slot position of query row 0 —
    the chunked-prefill continuation case (DESIGN.md §12), where a chunk of
    queries at absolute positions ``offset .. offset+T-1`` attends a cache
    of S >= offset+T key slots. Zero (the default) is the ordinary
    self-attention prefill where row index == absolute position."""
    b, hq, t, d = q.shape
    _, hkv, s_len, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    assert t % block_q == 0 and s_len % block_kv == 0, (
        f"(T={t}, S={s_len}) not divisible by blocks "
        f"({block_q},{block_kv}); pad at the ops layer")
    if start is None:
        start = jnp.zeros((b,), jnp.int32)
    if q_offset is None:
        q_offset = jnp.zeros((b,), jnp.int32)
    n_q, n_kv = t // block_q, s_len // block_kv

    kernel = functools.partial(
        _flash_prefill_kernel, n_kv=n_kv, block_q=block_q,
        block_kv=block_kv, sm_scale=sm_scale, window=window,
        softcap=softcap, out_dtype=q.dtype)
    # start / q_offset are per-row scalars: scalar-prefetched into SMEM
    # (a [1, 1] VMEM block over [B, 1] is not a legal TPU tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, h, i, j, st, qo: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bb, h, i, j, st, qo: (bb, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bb, h, i, j, st, qo: (bb, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, h, i, j, st, qo: (bb, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),    # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),    # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),      # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        name="flash_prefill",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, t, d), q.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(start, jnp.int32).reshape(b),
      jnp.asarray(q_offset, jnp.int32).reshape(b), q, k, v)


# ---------------------------------------------------------------------------
# packed (cu_seqlens) prefill
# ---------------------------------------------------------------------------

def _packed_online_update(s, mask, v, m_ref, l_ref, acc_ref):
    """Online-softmax step with an explicit probability mask. The packed
    kernel needs it because a computed block can be *fully* masked for some
    real query rows (a key block that only covers earlier segments): with
    m still at NEG_INF, ``exp(s - m) = exp(0) = 1`` would silently count
    every masked key. Zeroing p through the mask keeps those rows exact;
    the plain prefill kernel never hits this (the first computed block
    always holds key slot ``start``, valid for every real row)."""
    m_prev = m_ref[:, :1]                               # [M, 1]
    m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)                     # [M, 1]
    p = jnp.exp(s - m_cur) * mask.astype(jnp.float32)   # [M, Skv]
    l_cur = l_ref[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _flash_packed_kernel(q_ref, k_ref, v_ref, segq_ref, segk_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, n_kv: int, block_q: int,
                         block_kv: int, sm_scale: float, window: int,
                         softcap: float, out_dtype):
    i = pl.program_id(1)
    j = pl.program_id(2)
    qi0 = i * block_q
    kj0 = j * block_kv

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block skip: causal in absolute packed coordinates (a later segment's
    # keys always sit at higher absolute positions, so forward cross-
    # segment blocks fall out with the diagonal), plus the segment bound —
    # a key block wholly in earlier segments than every query row of this
    # block contributes nothing (segment ids are non-decreasing along the
    # packed axis, so the block extremes decide)
    run = kj0 <= qi0 + block_q - 1
    run &= segk_ref[0, block_kv - 1] >= segq_ref[0, 0]
    if window > 0:
        run &= kj0 + block_kv - 1 > qi0 - window

    @pl.when(run)
    def _compute():
        q = q_ref[0]                                    # [bq, D]
        k = k_ref[0]                                    # [bkv, D]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = _softcap(s, softcap)
        qi = qi0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kj = kj0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # block-diagonal mask from the row offsets: same segment + causal
        # (within a segment both positions shift by the same cu_seqlens
        # offset, so absolute comparisons ARE the logical causal/window
        # structure — the plain kernel's convention, DESIGN.md §12)
        mask = (kj <= qi) & (segq_ref[0][:, None] == segk_ref[0][None, :])
        if window > 0:
            mask &= kj > qi - window
        s = jnp.where(mask, s, NEG_INF)
        _packed_online_update(s, mask, v_ref[0], m_ref, l_ref, acc_ref)

    @pl.when(j == n_kv - 1)
    def _store():
        l = jnp.maximum(l_ref[:, :1], _L_EPS)
        o_ref[0] = (acc_ref[...] / l).astype(out_dtype)


def flash_prefill_packed_pallas(
    q: jax.Array,                 # [Hq, T, D] — packed tokens, head-major
    k: jax.Array,                 # [Hkv, T, D]
    v: jax.Array,                 # [Hkv, T, D]
    seg_ids: jax.Array,           # [1, T] int32, non-decreasing segment ids
    *,
    sm_scale: float,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """cu_seqlens-aware flash prefill over a PACKED ragged batch
    (DESIGN.md §12): T is the total token count of all concatenated
    requests, ``seg_ids[t]`` names the request owning packed position t
    (non-decreasing; padding tokens carry a sentinel id larger than every
    real segment). Masking is block-diagonal-causal — no query ever
    attends a key of another request. Returns o [Hq, T, D] in q.dtype."""
    hq, t, d = q.shape
    hkv = k.shape[0]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    assert t % block_q == 0 and t % block_kv == 0, (
        f"T={t} not divisible by blocks ({block_q},{block_kv}); "
        "pad at the ops layer")
    assert seg_ids.shape == (1, t), (seg_ids.shape, t)
    n_q, n_kv = t // block_q, t // block_kv

    kernel = functools.partial(
        _flash_packed_kernel, n_kv=n_kv, block_q=block_q,
        block_kv=block_kv, sm_scale=sm_scale, window=window,
        softcap=softcap, out_dtype=q.dtype)
    return pl.pallas_call(
        kernel,
        name="flash_prefill_packed",
        grid=(hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda h, i, j: (h // g, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda h, i, j: (h // g, j, 0)),
            pl.BlockSpec((1, block_q), lambda h, i, j: (0, i)),
            pl.BlockSpec((1, block_kv), lambda h, i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((hq, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),    # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),    # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),      # output accumulator
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, seg_ids, seg_ids)


# ---------------------------------------------------------------------------
# decode (paged KV)
# ---------------------------------------------------------------------------

def _paged_decode_kernel(tab_ref, len_ref, start_ref, layer_ref, q_ref,
                         k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                         n_log: int,
                         page: int, hkv: int, sm_scale: float, window: int,
                         softcap: float, out_dtype):
    bb = pl.program_id(0)
    j = pl.program_id(1)
    length = len_ref[bb]                                # current token's slot

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # page skip: any valid slot ⇔ page start <= length (causal), page end
    # past the row's left padding, and, with a window, page end inside it
    run = j * page <= length
    run &= (j + 1) * page - 1 >= start_ref[bb]
    if window > 0:
        run &= (j + 1) * page - 1 > length - window

    @pl.when(run)
    def _compute():
        g = q_ref.shape[2]
        kk = j * page + jax.lax.broadcasted_iota(jnp.int32, (g, page), 1)
        mask = (kk <= length) & (kk >= start_ref[bb])
        if window > 0:
            mask &= kk > length - window
        # the page block holds every KV head (a one-head slice of the
        # [page, Hkv, D] page is not a legal TPU tile); each head is a
        # strided read of it with its own online-softmax state
        for h in range(hkv):
            q = q_ref[0, h]                             # [G, D]
            k = k_ref[0, 0, :, h, :]                    # [page, D]
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(mask, _softcap(s, softcap), NEG_INF)
            _online_update(s, v_ref[0, 0, :, h, :], m_ref.at[h],
                           l_ref.at[h], acc_ref.at[h])

    @pl.when(j == n_log - 1)
    def _store():
        for h in range(hkv):
            l = jnp.maximum(l_ref[h, :, :1], _L_EPS)
            o_ref[0, h] = (acc_ref[h] / l).astype(out_dtype)


def paged_decode_pallas(
    q: jax.Array,                 # [B, Hkv, G, D] — one token, grouped heads
    k_pages: jax.Array,           # [L, P, page, Hkv, D] stacked page pools
    v_pages: jax.Array,           # [L, P, page, Hkv, D]
    block_table: jax.Array,       # [B, n_log] int32: logical → physical page
    lengths: jax.Array,           # [B] int32 — absolute slot of the new token
    start: jax.Array,             # [B] int32 — first real (non-pad) slot
    layer: jax.Array,             # [1] int32 — which layer's pool to read
    *,
    sm_scale: float,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    """One-token decode attention over layer ``layer`` of a paged KV pool
    stacked over layers. The block table and the layer index are
    scalar-prefetched so they drive the KV page DMA index map: logical
    page ``j`` of row ``b`` is fetched from page ``block_table[b, j]`` of
    layer ``layer``, all KV heads at once, and no other page of the pool is
    read. Returns o [B, Hkv, G, D] in q.dtype. The new token's K/V must
    already be scattered into the pool (slot ``lengths[b]`` of that
    layer). The operands open with the block table, lengths and starts,
    which is how a trace reader finds this op."""
    b, hkv, g, d = q.shape
    _, _, page, hkv2, _ = k_pages.shape
    assert hkv2 == hkv, (k_pages.shape, q.shape)
    n_log = block_table.shape[1]

    kernel = functools.partial(
        _paged_decode_kernel, n_log=n_log, page=page, hkv=hkv,
        sm_scale=sm_scale, window=window, softcap=softcap,
        out_dtype=q.dtype)

    def kv_map(bb, j, tab, ln, st, ly):
        return (ly[0], tab[bb, j], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, n_log),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d),
                         lambda bb, j, tab, ln, st, ly: (bb, 0, 0, 0)),
            pl.BlockSpec((1, 1, page, hkv, d), kv_map),
            pl.BlockSpec((1, 1, page, hkv, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d),
                               lambda bb, j, tab, ln, st, ly: (bb, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 128), jnp.float32),     # running max m
            pltpu.VMEM((hkv, g, 128), jnp.float32),     # running sum l
            pltpu.VMEM((hkv, g, d), jnp.float32),       # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, lengths, start, layer, q, k_pages, v_pages)
