"""Public wrappers for the flash-attention kernels (DESIGN.md §10).

`flash_attention` takes the model layout (``q [B, T, Hq, D]``,
``k/v [B, S, Hkv, D]``), transposes to the kernel's head-major layout,
pads T/S to the block grid (padded KV slots sit at absolute positions
``>= S`` and are causally unreachable from any real query; padded query
rows are sliced off), and dispatches. Block shapes default to a VMEM-aware
heuristic; with ``REPRO_AUTOTUNE=1`` the measured autotuner picks them
under the ``attn_flash`` op tag with `m_bucket()`-bucketed T keys (decode
and prefill sequence lengths never share an entry, mirroring the GEMM
wrappers).

`flash_ok` is the VMEM guard: callers (``models.attention``) fall back to
the chunked XLA path when even the smallest legal block pair would not
fit — the kernel never partially materializes.

`paged_decode_attention` wraps the block-table decode kernel, which reads
one layer of a pool stacked over layers; a contiguous cache is served by
the same wrapper through an identity block table
(`identity_block_table`) over a one-layer stack, which is what makes
paged-vs-contiguous decode bit-identical: one kernel, one page-visit
order, only the physical page layout differs.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.sta import SUBLANE
from repro.kernels.attn.kernel import (flash_prefill_packed_pallas,
                                       flash_prefill_pallas,
                                       paged_decode_pallas)
from repro.kernels.attn.ref import (flash_prefill_ref, packed_prefill_ref,
                                    paged_decode_ref)
from repro.kernels.common import (KERNEL_VMEM_BUDGET, default_interpret,
                                  round_up)

__all__ = ["flash_attention", "packed_flash_attention",
           "paged_decode_attention", "flash_ok", "paged_decode_ok",
           "identity_block_table", "DEFAULT_PAGE", "PACKED_PAD_SEG"]

# segment-id sentinel for packed-batch padding tokens: larger than any real
# segment, so pad rows match nothing and the non-decreasing block-skip
# invariant holds (DESIGN.md §12)
PACKED_PAD_SEG = 2 ** 30

# default KV page size (slots) when the config leaves kv_page_size unset —
# one f32 page of 64 slots × 128 head dim is half an MXU tile per head
DEFAULT_PAGE = 64


def _footprint(bq: int, bkv: int, d: int, itemsize: int) -> int:
    """Prefill VMEM working set: q/k/v tiles + score tile + (m, l, acc)
    f32 scratch."""
    return ((bq * d + 2 * bkv * d) * itemsize
            + bq * bkv * 4 + bq * d * 4 + 2 * bq * 128 * 4)


def _heuristic_blocks(t: int, s: int, d: int, itemsize: int
                      ) -> Tuple[int, int]:
    bq = min(128, round_up(max(t, 1), SUBLANE))
    bkv = min(128, round_up(max(s, 1), SUBLANE))
    while (_footprint(bq, bkv, d, itemsize) > KERNEL_VMEM_BUDGET
           and bkv > SUBLANE):
        bkv //= 2
    while (_footprint(bq, bkv, d, itemsize) > KERNEL_VMEM_BUDGET
           and bq > SUBLANE):
        bq //= 2
    return bq, bkv


def flash_ok(t: int, s: int, d: int, itemsize: int) -> bool:
    """Whether the flash kernel applies: the minimal legal block pair fits
    the VMEM budget (it always does for transformer head dims; a pathologic
    head_dim opts back into the chunked XLA path)."""
    return _footprint(SUBLANE, SUBLANE, d, itemsize) <= KERNEL_VMEM_BUDGET


def decode_footprint(gp: int, page: int, hkv: int, d: int,
                     itemsize: int) -> int:
    """Decode VMEM working set: the q/out blocks and one KV page of every
    head, one head's score tile, and the per-head (m, l, acc) f32
    scratch."""
    return ((2 * hkv * gp * d + 2 * page * hkv * d) * itemsize
            + gp * page * 4 + hkv * (2 * gp * 128 + gp * d) * 4)


def paged_decode_ok(page: int, hkv: int, d: int, itemsize: int) -> bool:
    """VMEM guard for the decode kernel: the page is its KV tile size, and
    unlike the prefill blocks it comes straight from user config
    (``kv_page_size`` / ``--kv-page-size``), so an oversized page must be
    rejected up front (contiguous decode falls back to the XLA path; the
    paged engine refuses at pool construction) rather than failing in the
    Mosaic lowering mid-serving. Budgeted at the worst-case resident query
    block (SKINNY_M_MAX rows per head)."""
    from repro.kernels.common import SKINNY_M_MAX
    return decode_footprint(round_up(SKINNY_M_MAX, SUBLANE), page, hkv, d,
                            itemsize) <= KERNEL_VMEM_BUDGET


def _autotuned_blocks(t: int, s: int, d: int, dtype, window: int,
                      softcap: float, interpret: bool, measure: bool
                      ) -> Tuple[int, int]:
    """Measured (block_q, block_kv) under the ``attn_flash`` op tag.
    Candidates are the heuristic choice and its half/double neighborhood,
    VMEM-filtered; (bq, d, bkv) triples reuse the GEMM cache machinery
    (m = T is bucketed, so decode-shaped and prefill-shaped calls keep
    distinct entries)."""
    import numpy as np

    from repro.kernels import autotune

    itemsize = np.dtype(dtype).itemsize
    bq0, bkv0 = _heuristic_blocks(t, s, d, itemsize)
    cands = []
    for fq in (1.0, 0.5, 2.0):
        for fkv in (1.0, 0.5, 2.0):
            bq = max(SUBLANE, min(int(bq0 * fq), round_up(max(t, 1), SUBLANE)))
            bkv = max(SUBLANE, min(int(bkv0 * fkv),
                                   round_up(max(s, 1), SUBLANE)))
            bq, bkv = round_up(bq, SUBLANE), round_up(bkv, SUBLANE)
            c = (bq, d, bkv)
            if c not in cands and _footprint(bq, bkv, d, itemsize) \
                    <= KERNEL_VMEM_BUDGET:
                cands.append(c)
    if not cands:
        cands = [(bq0, d, bkv0)]

    def make_fn(shape):
        bq, _, bkv = shape
        rng = np.random.default_rng(0)
        tp, sp = round_up(t, bq), round_up(s, bkv)
        q = jnp.asarray(rng.standard_normal((1, 1, tp, d)), dtype)
        k = jnp.asarray(rng.standard_normal((1, 1, sp, d)), dtype)
        v = jnp.asarray(rng.standard_normal((1, 1, sp, d)), dtype)
        return lambda: flash_prefill_pallas(
            q, k, v, sm_scale=1.0 / math.sqrt(d), window=window,
            softcap=softcap, block_q=bq, block_kv=bkv, interpret=interpret)

    name = "attn_flash" + ("_interp" if interpret else "")
    tag = f"w{1 if window > 0 else 0}+sc{1 if softcap > 0 else 0}"
    bq, _, bkv = autotune.autotune_block_shape(
        name, t, d, s, dtype, make_fn, epilogue_tag=tag,
        candidates=cands, itemsize=itemsize, measure=measure)
    return bq, bkv


def flash_attention(
    q: jax.Array,                 # [B, T, Hq, D] (model layout)
    k: jax.Array,                 # [B, S, Hkv, D]
    v: jax.Array,                 # [B, S, Hkv, D]
    start: Optional[jax.Array] = None,    # [B] int32 — first real key slot
    *,
    q_offset: Optional[jax.Array] = None,  # [B] int32 — abs pos of q row 0
    sm_scale: Optional[float] = None,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 0,
    block_kv: int = 0,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
    autotune: Optional[bool] = None,
) -> jax.Array:
    """Causal flash attention, model layout in/out ([B, T, Hq, D]).

    start [B]: absolute index of the first real key per row (left-padded
    ragged batches, DESIGN.md §5); keys below it are masked and queries
    below it produce garbage rows the caller already ignores. The mask is
    _mask_bias's qpos/kpos convention in absolute coordinates.

    q_offset [B]: absolute key-slot position of query row 0 — lets a
    chunked-prefill continuation (T chunk rows, S cache slots, DESIGN.md
    §12) reuse the same kernel; defaults to 0 (self-attention prefill).
    """
    b, t, hq, d = q.shape
    s_len = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = default_interpret()
    start2 = (None if start is None
              else jnp.asarray(start, jnp.int32).reshape(b, 1))
    qoff2 = (None if q_offset is None
             else jnp.asarray(q_offset, jnp.int32).reshape(b, 1))
    qh = jnp.moveaxis(q, 2, 1)                          # [B, Hq, T, D]
    kh = jnp.moveaxis(k, 2, 1)
    vh = jnp.moveaxis(v, 2, 1)
    if not use_kernel:
        o = flash_prefill_ref(qh, kh, vh, start2, qoff2, sm_scale=sm_scale,
                              window=window, softcap=softcap)
        return jnp.moveaxis(o, 1, 2)

    if block_q and block_kv:
        bq, bkv = block_q, block_kv
    else:
        if autotune is None:
            from repro.kernels.autotune import autotune_enabled
            autotune = autotune_enabled()
        if autotune:
            measure = not isinstance(q, jax.core.Tracer)
            bq, bkv = _autotuned_blocks(t, s_len, d, q.dtype, window,
                                        softcap, interpret, measure)
        else:
            bq, bkv = _heuristic_blocks(t, s_len, d, q.dtype.itemsize)
    tp, sp = round_up(t, bq), round_up(s_len, bkv)
    if tp != t:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    if sp != s_len:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, sp - s_len), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, sp - s_len), (0, 0)))
    o = flash_prefill_pallas(qh, kh, vh, start2, qoff2, sm_scale=sm_scale,
                             window=window, softcap=softcap, block_q=bq,
                             block_kv=bkv, interpret=interpret)
    return jnp.moveaxis(o[:, :, :t], 1, 2)


def packed_flash_attention(
    q: jax.Array,                 # [T, Hq, D] — packed model layout
    k: jax.Array,                 # [T, Hkv, D]
    v: jax.Array,                 # [T, Hkv, D]
    seg_ids: jax.Array,           # [T] int32, non-decreasing segment ids
    *,
    sm_scale: Optional[float] = None,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 0,
    block_kv: int = 0,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Block-diagonal-causal flash attention over a PACKED ragged batch
    (DESIGN.md §12): T = total tokens of all concatenated requests,
    ``seg_ids[t]`` names the owning request. No query crosses a segment
    boundary and no pad row reaches a GEMM with real weight — pad tokens
    are re-labelled `PACKED_PAD_SEG` here, so even caller-supplied pad ids
    can't collide with a real segment. Returns [T, Hq, D] in q.dtype;
    rows whose mask is empty (padding) hold garbage the caller never
    gathers."""
    t, hq, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = default_interpret()
    seg_ids = jnp.asarray(seg_ids, jnp.int32).reshape(1, t)
    qh = jnp.moveaxis(q, 1, 0)                          # [Hq, T, D]
    kh = jnp.moveaxis(k, 1, 0)
    vh = jnp.moveaxis(v, 1, 0)
    if not use_kernel:
        o = packed_prefill_ref(qh, kh, vh, seg_ids[0], sm_scale=sm_scale,
                               window=window, softcap=softcap)
        return jnp.moveaxis(o, 0, 1)

    if block_q and block_kv:
        bq, bkv = block_q, block_kv
    else:
        bq, bkv = _heuristic_blocks(t, t, d, q.dtype.itemsize)
        bq = bkv = min(bq, bkv)    # one padded T must serve both grids
    lcm = bq * bkv // math.gcd(bq, bkv)
    tp = round_up(t, lcm)
    if tp != t:
        pad = ((0, 0), (0, tp - t), (0, 0))
        qh, kh, vh = jnp.pad(qh, pad), jnp.pad(kh, pad), jnp.pad(vh, pad)
        seg_ids = jnp.pad(seg_ids, ((0, 0), (0, tp - t)),
                          constant_values=PACKED_PAD_SEG)
    o = flash_prefill_packed_pallas(qh, kh, vh, seg_ids, sm_scale=sm_scale,
                                    window=window, softcap=softcap,
                                    block_q=bq, block_kv=bkv,
                                    interpret=interpret)
    return jnp.moveaxis(o[:, :t], 0, 1)


def identity_block_table(b: int, n_log: int) -> jax.Array:
    """Block table mapping row ``b``'s logical page ``j`` to physical page
    ``b * n_log + j`` — a contiguous [B, S, H, D] cache reshaped to
    [B · n_log, page, H, D] is exactly this layout."""
    return (jnp.arange(b, dtype=jnp.int32)[:, None] * n_log
            + jnp.arange(n_log, dtype=jnp.int32)[None, :])


def paged_decode_attention(
    q: jax.Array,                 # [B, Hkv, G, D]
    k_pages: jax.Array,           # [L, P, page, Hkv, D] or [P, page, Hkv, D]
    v_pages: jax.Array,           # same shape as k_pages
    block_table: jax.Array,       # [B, n_log] int32
    lengths: jax.Array,           # [B] int32
    start: Optional[jax.Array] = None,    # [B] int32
    *,
    layer: Union[int, jax.Array] = 0,
    sm_scale: Optional[float] = None,
    window: int = 0,
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
) -> jax.Array:
    """One-token decode over a paged (or identity-table contiguous) KV
    cache. The pools are either stacked over layers, [L, P, page, Hkv, D],
    with ``layer`` (a traced scalar is fine) naming the layer to attend,
    or one layer's [P, page, Hkv, D] pool, which is the stacked form with
    L = 1 and layer 0 — one kernel serves both. Query rows (the GQA group,
    G ≤ 32 — `skinny_ok` gates upstream) pad to the sublane quantum; pad
    rows are sliced off."""
    b, hkv, g, d = q.shape
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = default_interpret()
    if start is None:
        start = jnp.zeros((b,), jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if not use_kernel:
        return paged_decode_ref(q, k_pages[layer[0]], v_pages[layer[0]],
                                block_table, lengths, start,
                                sm_scale=sm_scale, window=window,
                                softcap=softcap)
    gp = round_up(g, SUBLANE)
    qp = q if gp == g else jnp.pad(q, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    o = paged_decode_pallas(qp, k_pages, v_pages,
                            jnp.asarray(block_table, jnp.int32), lengths,
                            start, layer, sm_scale=sm_scale, window=window,
                            softcap=softcap, interpret=interpret)
    return o[:, :, :g]
