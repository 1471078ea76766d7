"""jnp oracles for the flash-attention kernels (DESIGN.md §10).

The prefill oracle materializes the full ``[B, Hq, T, S]`` score tensor and
runs a plain softmax — exactly what the flash kernel must never do — so
fused/unfused parity is a real structural check. The decode oracle gathers
the paged pool back into a contiguous [B, S, Hkv, D] cache through the
block table and reuses the same quadratic math.

Mask convention is `models.attention._mask_bias`'s, expressed in absolute
key/query slots: valid ⇔ ``k_abs <= q_abs`` ∧ ``k_abs >= start[b]``
(∧ ``k_abs > q_abs - window``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.attn.kernel import NEG_INF

__all__ = ["flash_prefill_ref", "packed_prefill_ref", "paged_decode_ref",
           "gather_pages"]


def _softcap(s: jax.Array, cap: float) -> jax.Array:
    return cap * jnp.tanh(s / cap) if cap > 0 else s


def flash_prefill_ref(
    q: jax.Array,                 # [B, Hq, T, D]
    k: jax.Array,                 # [B, Hkv, S, D]
    v: jax.Array,                 # [B, Hkv, S, D]
    start: Optional[jax.Array] = None,    # [B, 1] int32
    q_offset: Optional[jax.Array] = None,  # [B, 1] int32
    *,
    sm_scale: float,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    """Quadratic reference: full score tensor + plain softmax. ``q_offset``
    shifts query row 0 to that absolute key slot (chunked-prefill
    continuation, DESIGN.md §12)."""
    b, hq, t, d = q.shape
    hkv, s_len = k.shape[1], k.shape[2]
    g = hq // hkv
    if start is None:
        start = jnp.zeros((b, 1), jnp.int32)
    if q_offset is None:
        q_offset = jnp.zeros((b, 1), jnp.int32)
    kg = jnp.repeat(k, g, axis=1)                       # [B, Hq, S, D]
    vg = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q, kg,
                   preferred_element_type=jnp.float32) * sm_scale
    s = _softcap(s, softcap)
    qi = (jnp.arange(t)[None, :] + q_offset)[:, None, :, None]
    kj = jnp.arange(s_len)[None, None, None, :]
    mask = (kj <= qi) & (kj >= start[:, None, :, None])
    if window > 0:
        mask &= kj > qi - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhts,bhsd->bhtd", p.astype(v.dtype), vg,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def packed_prefill_ref(
    q: jax.Array,                 # [Hq, T, D] — packed tokens, head-major
    k: jax.Array,                 # [Hkv, T, D]
    v: jax.Array,                 # [Hkv, T, D]
    seg_ids: jax.Array,           # [T] int32, non-decreasing segment ids
    *,
    sm_scale: float,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    """Quadratic oracle for the packed (cu_seqlens) prefill kernel
    (DESIGN.md §12): block-diagonal-causal mask — a query attends a key iff
    they share a segment id and the key's packed position is not later.
    Within a segment both positions carry the same cu_seqlens offset, so
    absolute comparisons reproduce the per-request causal/window ladder."""
    hq, t, d = q.shape
    hkv = k.shape[0]
    g = hq // hkv
    seg_ids = jnp.asarray(seg_ids, jnp.int32).reshape(t)
    kg = jnp.repeat(k, g, axis=0)                       # [Hq, T, D]
    vg = jnp.repeat(v, g, axis=0)
    s = jnp.einsum("htd,hsd->hts", q, kg,
                   preferred_element_type=jnp.float32) * sm_scale
    s = _softcap(s, softcap)
    qi = jnp.arange(t)[:, None]
    kj = jnp.arange(t)[None, :]
    mask = (kj <= qi) & (seg_ids[:, None] == seg_ids[None, :])
    if window > 0:
        mask &= kj > qi - window
    s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no valid key (padding sentinels) get a uniform softmax over
    # NEG_INF scores — garbage the caller never gathers
    o = jnp.einsum("hts,hsd->htd", p.astype(v.dtype), vg,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def gather_pages(pages: jax.Array, block_table: jax.Array,
                 layer: Optional[jax.Array] = None) -> jax.Array:
    """[P, page, H, D] pool + [B, n_log] table → contiguous [B, S, H, D].
    With ``layer``, ``pages`` is a pool stacked over layers,
    [L, P, page, H, D], and the rows come from that layer in one gather —
    the layer's pool is never sliced out first."""
    b, n_log = block_table.shape
    page, h, d = pages.shape[-3:]
    gathered = (pages[block_table] if layer is None
                else pages[layer, block_table])        # [B, n_log, page, H, D]
    return gathered.reshape(b, n_log * page, h, d)


def paged_decode_ref(
    q: jax.Array,                 # [B, Hkv, G, D]
    k_pages: jax.Array,           # [P, page, Hkv, D]
    v_pages: jax.Array,           # [P, page, Hkv, D]
    block_table: jax.Array,       # [B, n_log] int32
    lengths: jax.Array,           # [B] int32
    start: jax.Array,             # [B] int32
    *,
    sm_scale: float,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    """Gather-then-attend reference for the paged decode kernel."""
    b, hkv, g, d = q.shape
    k = gather_pages(k_pages, block_table)              # [B, S, Hkv, D]
    v = gather_pages(v_pages, block_table)
    s = jnp.einsum("bhgd,bshd->bhgs", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    s = _softcap(s, softcap)
    kk = jnp.arange(k.shape[1])[None, :]
    valid = (kk <= lengths[:, None]) & (kk >= start[:, None])
    if window > 0:
        valid &= kk > (lengths[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)
