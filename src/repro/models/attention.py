"""GQA/MQA attention: fused Pallas flash path (DESIGN.md §10), chunked
(memory-efficient) XLA path for long prefill, naive oracle, cached decode.

Backend dispatch (``ModelConfig.attn_impl``): the **flash** kernel blocks
over KV with an online softmax — the ``[B, H, T, S]`` score tensor never
materializes — and handles causal + sliding-window + ragged left-pad
masking from the same qpos/kpos convention as `_mask_bias`, so ragged
serving batches stay token-identical. "auto" takes it whenever the Pallas
route is active (single device, float operands, VMEM guard passes); the
**chunked** path unrolls q-chunks in Python and scans only the kv-chunks
each q-chunk attends to; **naive** is the quadratic oracle. Decode routes
through the paged flash kernel (a contiguous cache is an identity block
table); `paged_decode_attention_apply` is the true paged-pool variant the
continuous-batching engine runs, on one layer of the stacked pool its
layer loop carries. KV heads are never materialized at
Hq width (GQA grouping stays factored) on any path.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.core.dbb import DbbWeight
from repro.dist.mesh_ctx import current_mesh, shard_tp
from repro.kernels.attn import (DEFAULT_PAGE, identity_block_table,
                                paged_decode_attention)
from repro.models.common import apply_rope, linear_init

__all__ = ["attention_init", "attention_apply", "packed_attention_apply",
           "chunk_attention_apply", "decode_attention_apply",
           "paged_decode_attention_apply", "verify_attention_apply",
           "paged_verify_attention_apply", "init_kv_cache"]

_NEG_INF = -1e30


def _lin(pp: Dict, x: jax.Array, cfg: Optional[ModelConfig] = None
         ) -> jax.Array:
    """Projection against a dense or DBB-packed weight, routed by the
    kernel dispatch registry. Packed weights (decode fast path, DESIGN.md
    §9) stream compressed through the DBB kernels with the bias fused into
    the epilogue — the dense [K, N] form never materializes, in HBM or
    VMEM. Dense weights keep the plain XLA matmul (shardable,
    differentiable) via ``dense_fused=False``, which the route guards
    honor (DESIGN.md §11)."""
    from repro.kernels import dispatch
    w = pp["w"]
    return dispatch.matmul(x, w, pp.get("b"),
                           out_dtype=x.dtype if isinstance(w, DbbWeight)
                           else None,
                           cfg=cfg, pallas=isinstance(w, DbbWeight),
                           dense_fused=False)


def _o_proj(pp: Dict, o2d: jax.Array, cfg: Optional[ModelConfig] = None
            ) -> jax.Array:
    """Row-parallel output projection epilogue. Inside a TP shard_map body
    (serving wrapper, DESIGN.md §14) the o_proj weight arrives row-sharded
    over the local heads' K slice, so the GEMM output is a partial sum —
    one chunked boundary all-reduce completes the attention block (chunked
    so XLA's async collective scheduler overlaps the first chunk's wire
    time with the later chunks' epilogue stores). Outside a shard body
    this is exactly `_lin`."""
    y = _lin(pp, o2d, cfg)
    if shard_tp() > 1:
        from repro.dist.collectives import overlapped_psum
        y = overlapped_psum(y, "model")
    return y


def attention_init(key, cfg: ModelConfig, dtype) -> Dict:
    d, hq, hkv, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                      cfg.resolved_head_dim)
    ks = jax.random.split(key, 4)
    return {
        "q_proj": linear_init(ks[0], d, hq * hd, dtype, bias=cfg.qkv_bias),
        "k_proj": linear_init(ks[1], d, hkv * hd, dtype, bias=cfg.qkv_bias),
        "v_proj": linear_init(ks[2], d, hkv * hd, dtype, bias=cfg.qkv_bias),
        "o_proj": linear_init(ks[3], hq * hd, d, dtype,
                              scale=1.0 / math.sqrt(hq * hd * 2 * cfg.num_layers)),
    }


def _project_qkv(p: Dict, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array):
    b, s, _ = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim

    q = _lin(p["q_proj"], x, cfg).reshape(b, s, hq, hd)
    k = _lin(p["k_proj"], x, cfg).reshape(b, s, hkv, hd)
    v = _lin(p["v_proj"], x, cfg).reshape(b, s, hkv, hd)
    if cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _scores(q, k, cfg: ModelConfig):
    """q: [B,T,Hkv,G,D], k: [B,S,Hkv,D] -> scores [B,Hkv,G,T,S] (f32).

    Operands stay in their storage dtype (bf16) — the MXU accumulates in
    f32 via preferred_element_type. Casting q/k to f32 up front would make
    XLA materialize (and on scan paths hoist) f32 copies of the whole KV
    cache: 2× the HBM traffic for zero precision gain on the MXU
    (EXPERIMENTS.md §Perf iteration 1)."""
    hd = q.shape[-1]
    s = jnp.einsum("bthgd,bshd->bhgts", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    if cfg.attn_logit_softcap > 0:
        c = cfg.attn_logit_softcap
        s = c * jnp.tanh(s / c)
    return s


def _mask_bias(qpos, kpos, window: int) -> jax.Array:
    """Additive bias [T, S] (1-D positions) or [B, T, S] (per-row ragged
    positions): causal (+ optional sliding window). Keys at negative
    positions are left-padding (ragged serving batches, DESIGN.md §5) and
    are masked out — for ordinary arange positions the term is a no-op."""
    q = qpos[..., :, None]
    kk = kpos[..., None, :]
    m = (kk <= q) & (kk >= 0)
    if window > 0:
        m &= kk > (q - window)
    return jnp.where(m, 0.0, _NEG_INF)


def _naive_attention(q, k, v, qpos, kpos, cfg: ModelConfig):
    """q:[B,T,Hq,D] k,v:[B,S,Hkv,D]; quadratic reference path.
    qpos/kpos: [T]/[S] shared positions, or [B,T]/[B,S] per-row (ragged)."""
    b, t, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, t, hkv, g, hd)
    bias = _mask_bias(qpos, kpos, cfg.sliding_window)
    if bias.ndim == 3:                     # [B,T,S] -> [B,1,1,T,S]
        bias = bias[:, None, None]
    s = _scores(qg, k, cfg) + bias
    p = jax.nn.softmax(s, axis=-1)
    # PV in storage dtype with f32 accumulation (flash-attention practice)
    o = jnp.einsum("bhgts,bshd->bthgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, t, hq, hd).astype(q.dtype)


def _chunked_causal_attention(q, k, v, cfg: ModelConfig, chunk: int):
    """No-waste blocked causal attention with running-softmax combine."""
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    assert s % chunk == 0, (s, chunk)
    n = s // chunk
    window = cfg.sliding_window
    qg = q.reshape(b, n, chunk, hkv, g, hd)
    kc = k.reshape(b, n, chunk, hkv, hd)
    vc = v.reshape(b, n, chunk, hkv, hd)
    # chunk-major for scan: [n, B, C, H, D]
    kc = jnp.moveaxis(kc, 1, 0)
    vc = jnp.moveaxis(vc, 1, 0)

    outs = []
    for i in range(n):                      # static unroll over q chunks
        j0 = 0
        if window > 0:
            j0 = max(0, (i * chunk - window) // chunk)
        qi = qg[:, i]                       # [B, C, Hkv, G, D] storage dtype
        qpos = i * chunk + jnp.arange(chunk)

        def step(carry, xs):
            m_run, l_run, acc = carry
            kj, vj, jidx = xs               # [B,C,H,D], [B,C,H,D], scalar
            sc = _scores(qi, kj, cfg)       # [B,H,G,T,S]
            kpos = jidx * chunk + jnp.arange(chunk)
            sc = sc + _mask_bias(qpos, kpos, window)
            m_new = jnp.maximum(m_run, sc.max(axis=-1))
            alpha = jnp.exp(m_run - m_new)
            pj = jnp.exp(sc - m_new[..., None])
            l_new = l_run * alpha + pj.sum(axis=-1)
            oj = jnp.einsum("bhgts,bshd->bhgtd", pj.astype(vj.dtype), vj,
                            preferred_element_type=jnp.float32)
            acc = acc * alpha[..., None] + oj
            return (m_new, l_new, acc), None

        shape_ml = (b, hkv, g, chunk)
        carry0 = (jnp.full(shape_ml, _NEG_INF, jnp.float32),
                  jnp.zeros(shape_ml, jnp.float32),
                  jnp.zeros((*shape_ml, hd), jnp.float32))
        xs = (kc[j0:i + 1], vc[j0:i + 1], jnp.arange(j0, i + 1))
        # flash-attention backward: recompute scores per kv-chunk instead
        # of saving [B,H,C,C] probability tensors for every chunk pair
        (m_f, l_f, acc), _ = jax.lax.scan(jax.checkpoint(step), carry0, xs)
        o = acc / jnp.maximum(l_f[..., None], 1e-30)   # [B,H,G,T,D]
        outs.append(jnp.moveaxis(o, 3, 1).reshape(b, chunk, hq, hd))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def _flash_backend(cfg: ModelConfig) -> bool:
    """Whether the fused flash kernel is the selected backend (delegates
    to the dispatch layer's route-family predicate, DESIGN.md §11)."""
    from repro.kernels.dispatch import flash_backend_active
    return flash_backend_active(cfg)


def _start_from_positions(positions: jax.Array, b: int) -> jax.Array:
    """Per-row first-real-key slot from the logical position ladder.
    Every caller builds positions as ``arange(s) - start`` (shared or
    per-row, DESIGN.md §5), so the leading entry recovers ``start``; for
    plain arange ladders this is zero and the pad mask is a no-op."""
    return jnp.broadcast_to(-positions[..., 0], (b,)).astype(jnp.int32)


def _attention_core(q, k, v, positions, cfg: ModelConfig,
                    ragged: bool = False) -> jax.Array:
    """Dispatch flash vs chunked vs naive on projected q/k/v. Returns
    o [B,S,Hq,D].

    The flash kernel serves every shape — ragged per-row positions ride in
    as ``start`` offsets (same masks as `_mask_bias`, never a [B,H,T,T]
    bias tensor). Without it, ragged=True (left-padded serving batch)
    forces the naive oracle with full batched masking and the chunked path
    assumes one shared arange position ladder."""
    from repro.kernels import dispatch
    return dispatch.attention(q, k, v, positions, cfg, ragged=ragged)


def attention_apply(p: Dict, cfg: ModelConfig, x: jax.Array,
                    positions: Optional[jax.Array] = None,
                    window_override: Optional[int] = None,
                    ragged: bool = False,
                    qkv: Optional[Tuple] = None) -> jax.Array:
    """Full-sequence (train / prefill) attention.

    ragged: positions are per-row (left-padded serving batch) — bypasses
    the chunked/TP fast paths, whose masks assume one shared ladder.
    qkv: optionally reuse already-projected (q, k, v) for these positions
    (prefill projects for the cache fill anyway); the TP branch ignores
    it — its projections are shard-local by construction."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    if window_override is not None:
        cfg = cfg.replace(sliding_window=window_override)
    mesh = current_mesh()
    # inside a TP shard_map body (serving wrapper, DESIGN.md §14) the cfg
    # is already localized and collectives ride on the enclosing mesh —
    # never nest the GSPMD-era _attention_tp shard_map
    tp = mesh.shape["model"] if (mesh is not None
                                 and "model" in mesh.axis_names
                                 and cfg.parallel != "dp"
                                 and shard_tp() == 0) else 1
    if tp > 1 and cfg.num_heads % tp == 0 and s > 1 and not ragged:
        return _attention_tp(p, cfg, x, positions, mesh, tp)
    q, k, v = qkv if qkv is not None else _project_qkv(p, cfg, x, positions)
    o = _attention_core(q, k, v, positions, cfg, ragged=ragged)
    b_, s_, hq, hd = o.shape
    return _o_proj(p["o_proj"], o.reshape(b_, s_, hq * hd), cfg)


def _attention_tp(p: Dict, cfg: ModelConfig, x: jax.Array,
                  positions: jax.Array, mesh, tp: int) -> jax.Array:
    """Explicit tensor-parallel attention (§Perf iterations 4+5).

    Q heads shard over "model" (hq % tp == 0, padded upstream when needed);
    K/V are computed per-shard from (small) replicated-or-gathered weights,
    and each local Q head gathers its own KV head — all score/softmax/PV
    work is shard-local, and the single boundary collective is the o_proj
    row-parallel psum in the storage dtype (bf16)."""
    from repro.models.mlp import (batch_axes_for,   # avoid import cycle
                                  seq_parallel_ok)

    b, s, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    hq_l = hq // tp
    g = hq // hkv
    ba = batch_axes_for(mesh, b)
    pos1d = positions[0] if positions.ndim > 1 else positions
    # sequence parallelism (§Perf iteration 7): residual stays seq-sharded;
    # block entry all-gathers, block exit reduce-scatters — same bytes as
    # the TP all-reduce at 2× the effective ring bandwidth, and norms /
    # residual adds run on 1/tp of the tokens.
    sp = seq_parallel_ok(cfg, s, tp)
    xspec = P(ba, "model", None) if sp else P(ba, None, None)

    # K/V projections stay column-sharded for COMPUTE (fractional heads are
    # fine for the GEMM); the small K/V activations are all-gathered so the
    # head-structured attention is shard-local. Computing K/V replicated
    # instead costs the full projection per device (+264 TFLOP/step on
    # qwen train_4k — §Perf iteration 6 refuted that variant).
    kvd = hkv * hd
    kv_shardable = kvd % tp == 0
    kv_w = P(None, "model") if kv_shardable else P(None, None)
    kv_b = P("model") if kv_shardable else P(None)
    wspecs = {
        "q_proj": {"w": P(None, "model")},
        "k_proj": {"w": kv_w},
        "v_proj": {"w": kv_w},
        "o_proj": {"w": P("model", None)},
    }
    if "b" in p["q_proj"]:
        wspecs["q_proj"]["b"] = P("model")
        wspecs["k_proj"]["b"] = kv_b
        wspecs["v_proj"]["b"] = kv_b

    def lin(pp, xx):
        y = xx @ pp["w"].astype(xx.dtype)
        if "b" in pp:
            y = y + pp["b"].astype(xx.dtype)
        return y

    def fn(xl, pl):
        bl = xl.shape[0]
        midx = jax.lax.axis_index("model")
        if sp:      # gather sequence shards at block entry (SP)
            xl = jax.lax.all_gather(xl, "model", axis=1, tiled=True)
        q = lin(pl["q_proj"], xl).reshape(bl, s, hq_l, hd)
        k = lin(pl["k_proj"], xl)                     # [b,s,kvd/tp]
        v = lin(pl["v_proj"], xl)
        if kv_shardable:
            k = jax.lax.all_gather(k, "model", axis=2, tiled=True)
            v = jax.lax.all_gather(v, "model", axis=2, tiled=True)
        k = k.reshape(bl, s, hkv, hd)
        v = v.reshape(bl, s, hkv, hd)
        if cfg.rope:
            q = apply_rope(q, pos1d[None, :], cfg.rope_theta)
            k = apply_rope(k, pos1d[None, :], cfg.rope_theta)
        # each local q head pairs with its kv head (present locally)
        kv_idx = (midx * hq_l + jnp.arange(hq_l)) // g
        k_sel = jnp.take(k, kv_idx, axis=2)           # [b,s,hq_l,hd]
        v_sel = jnp.take(v, kv_idx, axis=2)
        o = _attention_core(q, k_sel, v_sel, positions, cfg)
        y = o.reshape(bl, s, hq_l * hd) @ pl["o_proj"]["w"].astype(o.dtype)
        if sp:      # reduce-scatter back to the seq-sharded residual
            return jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                        tiled=True)
        return jax.lax.psum(y, "model")               # bf16 boundary reduce

    return shard_map(
        fn, mesh=mesh,
        in_specs=(xspec, wspecs),
        out_specs=xspec,
        check_vma=False)(x, {k: p[k] for k in wspecs})


def packed_attention_apply(p: Dict, cfg: ModelConfig, x: jax.Array,
                           seg_ids: jax.Array, positions: jax.Array,
                           qkv: Optional[Tuple] = None) -> jax.Array:
    """Packed (cu_seqlens) prefill attention (DESIGN.md §12): x [1, T, d]
    is a ragged batch's tokens concatenated along one axis, ``seg_ids [T]``
    names the owning request per packed position (non-decreasing; padding
    carries a larger sentinel), ``positions [1, T]`` the per-token logical
    position within its request (RoPE). Block-diagonal-causal by
    construction — no cross-request attention, no pad row in any GEMM with
    real extent. qkv optionally reuses the prefill body's projections."""
    q, k, v = qkv if qkv is not None else _project_qkv(p, cfg, x, positions)
    from repro.kernels import dispatch
    o = dispatch.packed_attention(q, k, v, seg_ids, cfg)
    b, t, hq, hd = o.shape
    return _o_proj(p["o_proj"], o.reshape(b, t, hq * hd), cfg)


def chunk_attention_apply(p: Dict, cfg: ModelConfig, q: jax.Array,
                          cache_k: jax.Array, cache_v: jax.Array,
                          offset: jax.Array) -> jax.Array:
    """Continuation attention for one chunk-prefilling row (DESIGN.md §12):
    q [1, C, Hq, D] are the chunk's projected queries at absolute cache
    positions ``offset .. offset+C-1``; cache_k/v [1, S, Hkv, D] is the
    row's full cache (earlier chunks + this chunk already scattered in).
    The causal mask bounds reads to slots <= qpos, all of which are real —
    packed-admitted rows have no left-pad. Returns the o_proj output
    [1, C, d]."""
    from repro.kernels import dispatch
    c, s = q.shape[1], cache_k.shape[1]
    hq, hd = q.shape[2], q.shape[3]
    route = dispatch.chunk_attention_route(
        cfg, t=c, s=s, d=hd, itemsize=q.dtype.itemsize,
        floating=jnp.issubdtype(q.dtype, jnp.floating))
    if route == "attn_flash":
        from repro.kernels.attn import flash_attention
        o = flash_attention(q, cache_k, cache_v,
                            q_offset=jnp.broadcast_to(offset, (1,)),
                            window=cfg.sliding_window,
                            softcap=cfg.attn_logit_softcap)
    else:
        qpos = offset + jnp.arange(c)
        kpos = jnp.arange(s)
        o = _naive_attention(q, cache_k, cache_v, qpos, kpos, cfg)
    return _o_proj(p["o_proj"], o.reshape(1, c, hq * hd), cfg)


# ---------------------------------------------------------------------------
# decode path (KV cache)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict:
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    L = cfg.num_layers
    return {
        "k": jnp.zeros((L, batch, max_len, hkv, hd), dtype),
        "v": jnp.zeros((L, batch, max_len, hkv, hd), dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def decode_attention_apply(p: Dict, cfg: ModelConfig, x: jax.Array,
                           cache_k: jax.Array, cache_v: jax.Array,
                           lengths: jax.Array,
                           window_override: Optional[int] = None,
                           ring: bool = False,
                           start: Optional[jax.Array] = None,
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode: x [B, 1, d]; cache_k/v [B, Smax, Hkv, D];
    lengths [B] current *absolute* context lengths (cache slot of the new
    token). Returns (y, new_k, new_v).

    start [B] (optional): index of the first real (non-pad) cache slot per
    row — left-padded ragged batches (DESIGN.md §5). RoPE positions shift
    to ``lengths - start`` (the logical context length) and slots below
    ``start`` are masked out, so a short prompt in a mixed batch decodes
    exactly as it would solo.

    ring=True treats the cache as a sliding-window ring buffer of size Smax:
    the new KV lands at ``lengths % Smax`` and every slot written so far is
    valid (window = Smax by construction). K entries are RoPE-rotated at
    their absolute positions, so relative offsets stay correct after wrap.
    """
    b = x.shape[0]
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = hq // hkv
    smax = cache_k.shape[1]
    rope_pos = lengths if start is None else lengths - start
    q, k, v = _project_qkv(p, cfg, x, rope_pos[:, None])
    ins = (lengths % smax) if ring else lengths

    def upd(cache, new, i):
        return jax.lax.dynamic_update_slice(cache, new, (i, 0, 0))
    new_k = jax.vmap(upd)(cache_k, k, ins)
    new_v = jax.vmap(upd)(cache_v, v, ins)

    # flash decode (DESIGN.md §10): the updated contiguous cache is a paged
    # pool under an identity block table — same kernel, same page-visit
    # order as the true paged pool, which is what makes paged serving
    # bit-identical to contiguous. The gate (flash backend + skinny-regime
    # G + page/VMEM guards) lives in the dispatch registry's attn_decode
    # domain (DESIGN.md §11); with kv_page_size unset the page adapts to
    # the cache length (largest power-of-two divisor up to DEFAULT_PAGE)
    # so arbitrary generate()/serve() cache sizes still take the kernel.
    from repro.kernels import dispatch
    page = cfg.kv_page_size or math.gcd(smax, DEFAULT_PAGE)
    decode_route = dispatch.decode_attention_route(
        cfg, group=g, head_dim=hd, itemsize=new_k.dtype.itemsize,
        page=page, smax=smax, kv_heads=hkv, ring=ring,
        floating=jnp.issubdtype(x.dtype, jnp.floating))
    if decode_route == "attn_decode_flash":
        window = (cfg.sliding_window if window_override is None
                  else window_override)
        n_log = smax // page
        kp = new_k.reshape(b * n_log, page, hkv, hd)
        vp = new_v.reshape(b * n_log, page, hkv, hd)
        o = paged_decode_attention(
            q.reshape(b, hkv, g, hd), kp, vp, identity_block_table(b, n_log),
            lengths, start, window=window, softcap=cfg.attn_logit_softcap)
        o = o.reshape(b, 1, hq * hd).astype(x.dtype)
        return _o_proj(p["o_proj"], o, cfg), new_k, new_v

    qg = q.reshape(b, 1, hkv, g, hd)
    sc = _scores(qg, new_k, cfg)                     # [B,H,G,1,Smax]
    kpos = jnp.arange(smax)[None, :]                 # [1, Smax]
    if ring:
        valid = kpos < jnp.minimum(lengths[:, None] + 1, smax)
    else:
        valid = kpos <= lengths[:, None]
        if start is not None:
            valid &= kpos >= start[:, None]      # pad slots never attended
        window = (cfg.sliding_window if window_override is None
                  else window_override)
        if window > 0:
            valid &= kpos > (lengths[:, None] - window)
    sc = sc + jnp.where(valid, 0.0, _NEG_INF)[:, None, None, None, :]
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhgts,bshd->bthgd", pr.astype(new_v.dtype), new_v,
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, 1, hq * hd).astype(x.dtype)
    y = _o_proj(p["o_proj"], o, cfg)
    return y, new_k, new_v


def verify_attention_apply(p: Dict, cfg: ModelConfig, x: jax.Array,
                           cache_k: jax.Array, cache_v: jax.Array,
                           lengths: jax.Array,
                           start: Optional[jax.Array] = None,
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Speculative VERIFY attention (DESIGN.md §15): x [B, T, d] carries
    the current token plus the T-1 draft tokens; their K/V land at
    absolute cache slots ``lengths .. lengths+T-1`` and every position
    attends the row's cache causally (self included) — one skinny-M
    batched step scores all T candidates through the unchanged cache
    instead of T sequential decode steps.

    Rejected drafts are rolled back by LENGTH ACCOUNTING alone: the
    engine advances ``length`` by the accepted count, future steps mask
    ``kpos > length`` and the next write overwrites the stale slots, so
    the pool itself is never touched twice. Same ragged contract as
    `decode_attention_apply`: RoPE at logical positions
    ``lengths - start + t``, pad slots below ``start`` never attended.
    """
    b, t, _ = x.shape
    hq, hd = cfg.num_heads, cfg.resolved_head_dim
    smax = cache_k.shape[1]
    st = jnp.zeros_like(lengths) if start is None else start
    qpos = (lengths - st)[:, None] + jnp.arange(t)[None, :]   # [B,T] logical
    q, k, v = _project_qkv(p, cfg, x, qpos)

    def upd(cache, new, i):
        return jax.lax.dynamic_update_slice(cache, new, (i, 0, 0))
    new_k = jax.vmap(upd)(cache_k, k.astype(cache_k.dtype), lengths)
    new_v = jax.vmap(upd)(cache_v, v.astype(cache_v.dtype), lengths)

    # logical key positions: slot s holds logical position s - start, so
    # pad slots sit below zero (masked) and the block's fresh keys line
    # up exactly under qpos — causal `kpos <= qpos` bounds each candidate
    # to its own prefix, matching a token-at-a-time decode bit-for-bit.
    kpos = jnp.arange(smax)[None, :] - st[:, None]            # [B, Smax]
    o = _naive_attention(q, new_k, new_v, qpos, kpos, cfg)
    return _o_proj(p["o_proj"], o.reshape(b, t, hq * hd), cfg), new_k, new_v


def paged_verify_attention_apply(p: Dict, cfg: ModelConfig, x: jax.Array,
                                 k_pages: jax.Array, v_pages: jax.Array,
                                 block_table: jax.Array, lengths: jax.Array,
                                 layer: jax.Array,
                                 start: Optional[jax.Array] = None,
                                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """`verify_attention_apply` against layer ``layer`` of the paged KV
    pools (DESIGN.md §10/§15): x [B, T, d]; k_pages/v_pages
    [L, P, page, Hkv, D], the whole stack the layer loop carries. The T
    candidate K/V scatter through the block table to their owning pages of
    that layer, in place, then the row's logical cache is gathered back
    from that layer (one gather) for the same naive masked attention —
    identical key order and identical f32 arithmetic as the contiguous
    twin, so paged and contiguous speculative serving stay bit-identical.
    Rows whose table points at the reserved dummy page (retired slots
    still stepping) write there harmlessly; logical page indices clamp so
    overshoot never runs off the table. Returns (y, k_pages, v_pages),
    the stacks with this layer's writes."""
    from repro.kernels.attn.ref import gather_pages
    b, t, _ = x.shape
    hq, hd = cfg.num_heads, cfg.resolved_head_dim
    page = k_pages.shape[2]
    n_log = block_table.shape[1]
    st = jnp.zeros_like(lengths) if start is None else start
    qpos = (lengths - st)[:, None] + jnp.arange(t)[None, :]   # [B,T] logical
    q, k, v = _project_qkv(p, cfg, x, qpos)

    slots = lengths[:, None] + jnp.arange(t)[None, :]         # [B,T] absolute
    logp = jnp.clip(slots // page, 0, n_log - 1)
    phys = jnp.take_along_axis(block_table, logp, axis=1)     # [B,T]
    off = slots % page
    k_pages = k_pages.at[layer, phys, off].set(k.astype(k_pages.dtype))
    v_pages = v_pages.at[layer, phys, off].set(v.astype(v_pages.dtype))

    krow = gather_pages(k_pages, block_table, layer)          # [B, S, Hkv, D]
    vrow = gather_pages(v_pages, block_table, layer)
    kpos = jnp.arange(n_log * page)[None, :] - st[:, None]
    o = _naive_attention(q, krow, vrow, qpos, kpos, cfg)
    return (_o_proj(p["o_proj"], o.reshape(b, t, hq * hd), cfg),
            k_pages, v_pages)


def paged_decode_attention_apply(p: Dict, cfg: ModelConfig, x: jax.Array,
                                 k_pages: jax.Array, v_pages: jax.Array,
                                 block_table: jax.Array, lengths: jax.Array,
                                 layer: jax.Array,
                                 window_override: Optional[int] = None,
                                 start: Optional[jax.Array] = None,
                                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against layer ``layer`` of the paged KV pools
    (DESIGN.md §10): x [B, 1, d]; k_pages/v_pages [L, P, page, Hkv, D], the
    whole stack the layer loop carries; block_table [B, n_log] maps each
    row's logical pages to physical pool pages. The new token's K/V are
    written at ``[layer, page, offset]`` in place and the decode kernel
    reads that layer's pages straight out of the stack. Returns
    (y, k_pages, v_pages), the stacks with this layer's writes.

    Same per-row contract as `decode_attention_apply`: ``lengths`` is the
    absolute cache slot of the new token, ``start`` the first real slot of
    a left-padded row. The new K/V scatter resolves the owning physical
    page through the table; rows whose table points at the reserved dummy
    page (retired slots still stepping inside a decode chunk) write there
    harmlessly, and the logical page index clamps so overshoot never runs
    off the table (mirroring the contiguous cache's clamped
    dynamic_update_slice)."""
    b = x.shape[0]
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = hq // hkv
    page = k_pages.shape[2]
    n_log = block_table.shape[1]
    rope_pos = lengths if start is None else lengths - start
    q, k, v = _project_qkv(p, cfg, x, rope_pos[:, None])

    logp = jnp.clip(lengths // page, 0, n_log - 1)
    phys = jnp.take_along_axis(block_table, logp[:, None], axis=1)[:, 0]
    off = lengths % page
    k_pages = k_pages.at[layer, phys, off].set(k[:, 0].astype(k_pages.dtype))
    v_pages = v_pages.at[layer, phys, off].set(v[:, 0].astype(v_pages.dtype))

    # the pool has no other decode path: the engine offers paged serving
    # only where the registry picks the kernel, which this asserts
    from repro.kernels import dispatch
    route = dispatch.decode_attention_route(
        cfg, group=g, head_dim=hd, itemsize=k_pages.dtype.itemsize,
        page=page, smax=n_log * page, kv_heads=hkv,
        floating=jnp.issubdtype(x.dtype, jnp.floating))
    if route != "attn_decode_flash":
        raise ValueError(f"paged KV decode needs the attn_decode_flash "
                         f"route; the registry picked {route!r}")
    window = (cfg.sliding_window if window_override is None
              else window_override)
    o = paged_decode_attention(
        q.reshape(b, hkv, g, hd), k_pages, v_pages, block_table, lengths,
        start, layer=layer, window=window, softcap=cfg.attn_logit_softcap)
    o = o.reshape(b, 1, hq * hd).astype(x.dtype)
    return _o_proj(p["o_proj"], o, cfg), k_pages, v_pages
