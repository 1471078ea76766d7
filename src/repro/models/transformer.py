"""Decoder-LM host: embeddings → scan-over-layers → final norm (→ LM head).

One host covers every assigned family:
  dense_lm / audio_lm / vlm_lm : attention + MLP blocks
  moe_lm                       : attention + MoE blocks (aux loss threaded)
  rwkv6                        : time-mix + channel-mix (attention-free)
  zamba2                       : Mamba2 backbone + one *shared* attention
                                 block applied every `shared_period` layers

Layers are scanned with stacked params (compile time O(1 layer)); the
zamba2 hybrid scans each Mamba group and interleaves the shared block in a
static Python loop. `remat` wraps the layer body per config.

`forward` returns hidden states (not logits): the LM head is applied by the
loss/serve layer so the vocab-parallel cross-entropy never materializes
unsharded logits.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.dist.mesh_ctx import shard_hint
from repro.models import attention as attn
from repro.models import mamba2 as m2
from repro.models import rwkv6 as rw
from repro.models.common import (dtype_of, embed_apply, embed_init,
                                 linear_init, norm_apply, norm_init)
from repro.models.mlp import mlp_apply, mlp_init
from repro.models.moe import moe_apply, moe_init

__all__ = ["init_params", "forward", "decode_step", "verify_step",
           "prefill", "prefill_packed", "prefill_continue", "init_cache",
           "lm_head_weight"]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    if cfg.family == "rwkv6":
        return rw.rwkv6_layer_init(ks[0], cfg, dtype)
    if cfg.family == "zamba2":
        return {"mamba": m2.mamba2_init(ks[0], cfg, dtype),
                "ln": norm_init(cfg.norm, cfg.d_model, dtype)}
    p = {
        "attn": attn.attention_init(ks[0], cfg, dtype),
        "ln_attn": norm_init(cfg.norm, cfg.d_model, dtype),
        "ln_mlp": norm_init(cfg.norm, cfg.d_model, dtype),
    }
    if cfg.family == "moe_lm":
        p["moe"] = moe_init(ks[1], cfg, dtype)
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg, dtype)
    return p


def _shared_block_init(key, cfg: ModelConfig, dtype) -> Dict:
    """Zamba2's shared attention+MLP block (one set of weights)."""
    ks = jax.random.split(key, 2)
    return {
        "attn": attn.attention_init(ks[0], cfg, dtype),
        "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg, dtype),
        "ln_attn": norm_init(cfg.norm, cfg.d_model, dtype),
        "ln_mlp": norm_init(cfg.norm, cfg.d_model, dtype),
    }


def init_params(key, cfg: ModelConfig) -> Dict:
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    layer_keys = jax.random.split(ks[0], cfg.num_layers)
    layers = jax.vmap(lambda k: _layer_init(k, cfg, dtype))(layer_keys)
    params: Dict[str, Any] = {
        "embed": embed_init(ks[1], cfg.vocab_size, cfg.d_model, dtype),
        "layers": layers,
        "final_norm": norm_init(cfg.norm, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = linear_init(ks[2], cfg.d_model, cfg.vocab_size,
                                        dtype)
    if cfg.family == "zamba2":
        params["shared_block"] = _shared_block_init(ks[3], cfg, dtype)
    return params


def lm_head_weight(params: Dict, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"]["table"].T
    return params["lm_head"]["w"]


# ---------------------------------------------------------------------------
# layer bodies
# ---------------------------------------------------------------------------

# Families whose layer blocks (attention + MLP) consume DbbWeight leaves
# directly through the DBB kernels — the packed-weight streaming fast path
# (DESIGN.md §9). SSM/hybrid time-mix and MoE expert einsums still need
# dense weights and keep the per-layer transient expand.
_STREAM_FAMILIES = ("dense_lm", "vlm_lm", "audio_lm")


def _stream_packed(cfg: ModelConfig) -> bool:
    """Whether packed layer weights can skip the per-layer dense expand:
    the attention/MLP blocks stream DbbWeight leaves straight through the
    DBB Pallas kernels (the dispatch registry's dbb routes, DESIGN.md
    §11), so the weight stays compressed end-to-end — HBM holds only
    values+bitmask and the kernel decompresses tiles in VMEM."""
    from repro.kernels.dispatch import pallas_route_active
    return cfg.family in _STREAM_FAMILIES and pallas_route_active(cfg)


def _unpack_layer(lp: Dict, cfg: ModelConfig) -> Dict:
    """Per-layer DBB decompression inside the scan body: the stacked
    weights stay packed in HBM; only the current layer's dense form is
    live (§Perf iteration 17). No-op for dense trees. Under the packed
    streaming fast path (DESIGN.md §9) even that per-layer transient is
    skipped — the kernels consume the compressed leaves directly."""
    if _stream_packed(cfg):
        return lp
    from repro.core.dbb_linear import maybe_decompress_tree
    return maybe_decompress_tree(lp, dtype=dtype_of(cfg))


def _attn_mlp_layer(lp: Dict, cfg: ModelConfig, x: jax.Array,
                    window_override: Optional[int] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    lp = _unpack_layer(lp, cfg)
    h = norm_apply(cfg.norm, lp["ln_attn"], x)
    x = x + attn.attention_apply(lp["attn"], cfg, h,
                                 window_override=window_override)
    h = norm_apply(cfg.norm, lp["ln_mlp"], x)
    if cfg.family == "moe_lm":
        y, aux = moe_apply(lp["moe"], cfg, h)
        return x + y, aux
    return x + mlp_apply(lp["mlp"], cfg, h), jnp.zeros((), jnp.float32)


def _wrap_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        # save matmul outputs (fastest bwd, largest live set)
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    # auto: real-size models checkpoint at layer boundaries plus the two
    # named fat MLP up-projections (§Perf iteration 8) — skipping their
    # recompute buys back ~50% of the remat flops for ~56 MB/layer/shard;
    # smoke configs skip remat entirely.
    if cfg.d_model >= 1024:
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "mlp_wi", "mlp_wg"))
    return fn


def _scan_layers(stacked: Any, x: jax.Array, body) -> Tuple[jax.Array, jax.Array]:
    """body(lp, x) -> (x, aux). Returns (x, aux_sum)."""
    def step(carry, lp):
        x, aux = carry
        x, a = body(lp, x)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux


# ---------------------------------------------------------------------------
# forward (train / prefill shapes)
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg: ModelConfig, tokens=None, embeds=None,
                  prefix_embeds=None) -> jax.Array:
    dtype = dtype_of(cfg)
    if embeds is not None:
        x = embeds.astype(dtype)
    else:
        x = embed_apply(params["embed"], tokens, dtype,
                        vocab_parallel=cfg.parallel != "dp")
        if cfg.family in ("dense_lm", "moe_lm", "vlm_lm"):
            x = x * (cfg.d_model ** 0.5)
    if prefix_embeds is not None:       # vlm: SigLIP patch embeddings
        x = jnp.concatenate([prefix_embeds.astype(dtype), x], axis=1)
    return x


def forward(params: Dict, cfg: ModelConfig, tokens=None, embeds=None,
            prefix_embeds=None, window_override: Optional[int] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Returns (hidden [B, S, d], moe aux loss scalar)."""
    x = _embed_inputs(params, cfg, tokens, embeds, prefix_embeds)
    from repro.dist.mesh_ctx import axis_size
    from repro.models.mlp import seq_parallel_ok
    if seq_parallel_ok(cfg, x.shape[1], axis_size("model")):
        # SP residual layout (the blocks gather/scatter at their edges)
        x = shard_hint(x, ("pod", "data"), "model", None)
    elif cfg.parallel == "dp":
        x = shard_hint(x, ("pod", "data", "model"), None, None)
    else:
        x = shard_hint(x, ("pod", "data"), None, None)

    if cfg.family == "rwkv6":
        body = _wrap_remat(
            lambda lp, xx: (rw.rwkv6_layer_apply(_unpack_layer(lp, cfg),
                                                 cfg, xx)[0],
                            jnp.zeros((), jnp.float32)), cfg)
        x, aux = _scan_layers(params["layers"], x, body)
    elif cfg.family == "zamba2":
        x, aux = _zamba2_forward(params, cfg, x, window_override)
    else:
        body = _wrap_remat(
            lambda lp, xx: _attn_mlp_layer(lp, cfg, xx, window_override), cfg)
        x, aux = _scan_layers(params["layers"], x, body)

    x = norm_apply(cfg.norm, params["final_norm"], x)
    return x, aux


def _zamba2_forward(params, cfg: ModelConfig, x, window_override=None):
    period = cfg.ssm.shared_period
    L = cfg.num_layers
    sb = params["shared_block"]
    aux = jnp.zeros((), jnp.float32)
    scfg = cfg.replace(family="dense_lm")

    def mamba_body(lp, xx):
        lp = _unpack_layer(lp, cfg)
        h = norm_apply(cfg.norm, lp["ln"], xx)
        y, _ = m2.mamba2_apply(lp["mamba"], cfg, h)
        return xx + y, jnp.zeros((), jnp.float32)

    body = _wrap_remat(mamba_body, cfg)
    # the shared block sits in the unrolled group loop — without its own
    # remat each invocation pins its full chunked-attention score tensors
    # (~5 GB/device per block on train_4k)
    shared_body = _wrap_remat(
        lambda sbp, xx: _attn_mlp_layer(sbp, scfg, xx,
                                        window_override=window_override),
        cfg)
    bounds = list(range(0, L, period)) + [L]
    for gi in range(len(bounds) - 1):
        g0, g1 = bounds[gi], bounds[gi + 1]
        group = jax.tree_util.tree_map(lambda a: a[g0:g1], params["layers"])
        x, _ = _scan_layers(group, x, body)
        if g1 < L or gi == len(bounds) - 2:
            x, _ = shared_body(sb, x)
    return x, aux


# ---------------------------------------------------------------------------
# caches, prefill and decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    dtype = dtype_of(cfg)
    if cfg.family == "rwkv6":
        return dict(rw.init_rwkv_state(cfg, batch, dtype),
                    length=jnp.zeros((batch,), jnp.int32))
    if cfg.family == "zamba2":
        n_groups = -(-cfg.num_layers // cfg.ssm.shared_period)
        d_in, h, p, n = m2._dims(cfg)
        cw = cfg.ssm.conv_width
        win = min(max_len, cfg.ssm.shared_window or max_len)
        return {
            "ssd": jnp.zeros((cfg.num_layers, batch, h, p, n), jnp.float32),
            "conv": jnp.zeros((cfg.num_layers, batch, cw - 1, d_in + 2 * n),
                              dtype),
            "shared_k": jnp.zeros((n_groups, batch, win,
                                   cfg.num_kv_heads, cfg.resolved_head_dim),
                                  dtype),
            "shared_v": jnp.zeros((n_groups, batch, win,
                                   cfg.num_kv_heads, cfg.resolved_head_dim),
                                  dtype),
            "length": jnp.zeros((batch,), jnp.int32),
        }
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((cfg.num_layers, batch, max_len, hkv, hd), dtype),
        "v": jnp.zeros((cfg.num_layers, batch, max_len, hkv, hd), dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def _scan_cached_layers(params: Dict, cfg: ModelConfig, x: jax.Array,
                        cache: Dict, attn_call) -> Tuple[jax.Array, Dict]:
    """Run the layer stack of a cached step (decode, speculative verify and
    the three prefills) and apply the final norm. Every such step shares
    this block — norm, attention, residual, norm, MLP or MoE, residual —
    and differs only in ``attn_call(lp, h, k, v, l) -> (y, k, v)``, so the
    paths cannot drift.

    The KV layout (DESIGN.md §10) decides what ``k``/``v`` are:

    * contiguous ``k``/``v`` [L, B, S, Hkv, D] scan with the layers; each
      layer gets and returns its own [B, S, Hkv, D] slice (``l`` is
      None);
    * paged ``k_pages``/``v_pages`` [L, P, page, Hkv, D] ride whole in
      the loop's carry. Each layer gets both stacks and its index ``l``,
      writes its new rows at ``[l, page, offset]`` in place, and hands the
      decode kernel the whole stack plus ``l``: no step slices a layer's
      pool out, writes it back or copies it. A params stack shorter than
      the pool (the speculative draft) runs the pool's first layers.

    Returns (hidden, cache with the new K/V)."""
    paged = "k_pages" in cache
    kk, vv = ("k_pages", "v_pages") if paged else ("k", "v")

    def block(lp, l, x, k, v):
        lp = _unpack_layer(lp, cfg)
        h = norm_apply(cfg.norm, lp["ln_attn"], x)
        y, k, v = attn_call(lp, h, k, v, l)
        x = x + y
        h = norm_apply(cfg.norm, lp["ln_mlp"], x)
        if cfg.family == "moe_lm":
            z, _ = moe_apply(lp["moe"], cfg, h)
            x = x + z
        else:
            x = x + mlp_apply(lp["mlp"], cfg, h)
        return x, k, v

    if paged:
        def step(carry, xs):
            return block(*xs, *carry), None

        n_layers = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
        (x, k, v), _ = jax.lax.scan(
            step, (x, cache[kk], cache[vv]),
            (params["layers"], jnp.arange(n_layers, dtype=jnp.int32)))
    else:
        def step(x, xs):
            lp, k, v = xs
            x, k, v = block(lp, None, x, k, v)
            return x, (k, v)

        x, (k, v) = jax.lax.scan(step, x, (params["layers"], cache[kk],
                                           cache[vv]))
    x = norm_apply(cfg.norm, params["final_norm"], x)
    return x, dict(cache, **{kk: k, vv: v})


def verify_step(params: Dict, cfg: ModelConfig, tokens: jax.Array,
                cache: Dict) -> Tuple[jax.Array, Dict]:
    """Speculative VERIFY: score T candidate tokens in ONE skinny-M
    batched step (DESIGN.md §15). ``tokens [B, T]`` carries the current
    token plus the T-1 draft tokens per row; every layer's K/V is written
    at cache slots ``length .. length+T-1`` and the returned hidden
    ``[B, T, d]`` yields the full model's distribution at each candidate
    position.

    ``cache["length"]`` is left UNTOUCHED: the caller advances it by the
    accepted count, which IS the rollback — stale K/V past the accepted
    prefix is masked by ``kpos <= length`` everywhere and overwritten by
    the next step, in both KV layouts."""
    assert cfg.family in ("dense_lm", "moe_lm", "vlm_lm",
                          "audio_lm"), cfg.family
    dtype = dtype_of(cfg)
    x = embed_apply(params["embed"], tokens, dtype,
                    vocab_parallel=cfg.parallel != "dp")
    if cfg.family in ("dense_lm", "moe_lm", "vlm_lm"):
        x = x * (cfg.d_model ** 0.5)
    start = cache.get("start")
    lengths = cache["length"]
    if "k_pages" in cache:
        table = cache["block_table"]
        return _scan_cached_layers(
            params, cfg, x, cache,
            lambda lp, h, kp, vp, l: attn.paged_verify_attention_apply(
                lp["attn"], cfg, h, kp, vp, table, lengths, l, start=start))
    return _scan_cached_layers(
        params, cfg, x, cache,
        lambda lp, h, ck, cv, l: attn.verify_attention_apply(
            lp["attn"], cfg, h, ck, cv, lengths, start=start))


def decode_step(params: Dict, cfg: ModelConfig, tokens: jax.Array,
                cache: Dict, embeds: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Dict]:
    """One new token for every sequence. tokens: [B] (or embeds [B,1,d]).
    Returns (hidden [B,1,d], updated cache).

    If the cache carries ragged-prefill offsets (``cache["start"]``, set by
    `prefill(start=...)`), attention masks the left-pad slots and shifts
    RoPE positions per row (DESIGN.md §5)."""
    dtype = dtype_of(cfg)
    if embeds is not None:
        x = embeds.astype(dtype)
    else:
        x = embed_apply(params["embed"], tokens[:, None], dtype,
                        vocab_parallel=cfg.parallel != "dp")
        if cfg.family in ("dense_lm", "moe_lm", "vlm_lm"):
            x = x * (cfg.d_model ** 0.5)

    if cfg.family == "rwkv6":
        def body(x, xs):
            lp, st = xs
            y, new_st = rw.rwkv6_decode_step(_unpack_layer(lp, cfg), cfg,
                                             x, st)
            return y, new_st

        st = {"wkv": cache["wkv"], "shift_tm": cache["shift_tm"],
              "shift_cm": cache["shift_cm"]}
        x, new_st = jax.lax.scan(body, x, (params["layers"], st))
        new_cache = dict(new_st, length=cache["length"] + 1)
        x = norm_apply(cfg.norm, params["final_norm"], x)
        return x, new_cache

    if cfg.family == "zamba2":
        return _zamba2_decode(params, cfg, x, cache)

    start = cache.get("start")
    if "k_pages" in cache:
        # paged KV cache (DESIGN.md §10): the block table / lengths /
        # starts are row-indexed and shared across layers (one allocation
        # serves all L pools at the same physical page index)
        table = cache["block_table"]
        x, new_cache = _scan_cached_layers(
            params, cfg, x, cache,
            lambda lp, h, kp, vp, l: attn.paged_decode_attention_apply(
                lp["attn"], cfg, h, kp, vp, table, cache["length"], l,
                start=start))
        return x, dict(new_cache, length=cache["length"] + 1,
                       start=(start if start is not None
                              else jnp.zeros_like(cache["length"])))

    x, new_cache = _scan_cached_layers(
        params, cfg, x, cache,
        lambda lp, h, ck, cv, l: attn.decode_attention_apply(
            lp["attn"], cfg, h, ck, cv, cache["length"], start=start))
    return x, dict(new_cache, length=cache["length"] + 1)


def _zamba2_decode(params, cfg: ModelConfig, x, cache):
    period = cfg.ssm.shared_period
    L = cfg.num_layers
    sb = params["shared_block"]
    win = cache["shared_k"].shape[2]

    def mamba_body(x, xs):
        lp, ssd, conv = xs
        lp = _unpack_layer(lp, cfg)
        h = norm_apply(cfg.norm, lp["ln"], x)
        y, (nssd, nconv) = m2.mamba2_apply(lp["mamba"], cfg, h, state=ssd,
                                           conv_ctx=conv)
        return x + y, (nssd, nconv)

    bounds = list(range(0, L, period)) + [L]
    new_ssd, new_conv = [], []
    new_sk, new_sv = [], []
    scfg = cfg.replace(family="dense_lm")
    for gi in range(len(bounds) - 1):
        g0, g1 = bounds[gi], bounds[gi + 1]
        sl = lambda a: a[g0:g1]
        x, (nssd, nconv) = jax.lax.scan(
            mamba_body, x,
            (jax.tree_util.tree_map(sl, params["layers"]),
             cache["ssd"][g0:g1], cache["conv"][g0:g1]))
        new_ssd.append(nssd)
        new_conv.append(nconv)
        if g1 < L or gi == len(bounds) - 2:
            h = norm_apply(cfg.norm, sb["ln_attn"], x)
            y, nk, nv = attn.decode_attention_apply(
                sb["attn"], scfg, h, cache["shared_k"][gi],
                cache["shared_v"][gi], cache["length"], ring=True)
            x = x + y
            h = norm_apply(cfg.norm, sb["ln_mlp"], x)
            x = x + mlp_apply(sb["mlp"], scfg, h)
            new_sk.append(nk)
            new_sv.append(nv)
    new_cache = {
        "ssd": jnp.concatenate(new_ssd, 0),
        "conv": jnp.concatenate(new_conv, 0),
        "shared_k": jnp.stack(new_sk, 0),
        "shared_v": jnp.stack(new_sv, 0),
        "length": cache["length"] + 1,
    }
    x = norm_apply(cfg.norm, params["final_norm"], x)
    return x, new_cache


def prefill(params: Dict, cfg: ModelConfig, tokens=None, embeds=None,
            prefix_embeds=None, cache: Optional[Dict] = None,
            start: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Dict]:
    """Full-context forward that also fills the cache (serving prefill).

    For attention archs this recomputes K/V per layer into the cache; for
    SSM/hybrid archs it runs the stateful forward and stores final states.

    start [B] (optional): per-row count of left-pad tokens in a ragged
    batch. Attention archs shift RoPE positions to ``t - start`` and mask
    the pad keys so every row prefills exactly as it would solo; the
    offsets ride in the returned cache (``cache["start"]``) for the decode
    steps (DESIGN.md §5). SSM/hybrid archs ignore the hint — their
    recurrent state consumes pads by construction, so ragged exactness
    there needs right-padding + state masking (not yet implemented).
    """
    x = _embed_inputs(params, cfg, tokens, embeds, prefix_embeds)
    b, s, _ = x.shape
    if cache is None:
        cache = init_cache(cfg, b, s)

    if cfg.family == "rwkv6":
        def body(x, lp):
            y, st = rw.rwkv6_layer_apply(_unpack_layer(lp, cfg), cfg, x)
            return y, st

        x, st = jax.lax.scan(body, x, params["layers"])
        cache = dict(st, length=cache["length"] + s)
        x = norm_apply(cfg.norm, params["final_norm"], x)
        return x, cache

    if cfg.family == "zamba2":
        return _zamba2_prefill(params, cfg, x, cache)

    # per-row ragged positions: pads (t < start) sit at negative logical
    # positions, which the attention mask excludes as keys
    positions = jnp.arange(s)[None, :]
    if start is not None:
        positions = positions - start[:, None]

    def attend(lp, h, ck, cv, l):
        q, k, v = attn._project_qkv(lp["attn"], cfg, h, positions)
        ck = jax.lax.dynamic_update_slice(ck, k, (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v, (0, 0, 0, 0))
        y = attn.attention_apply(lp["attn"], cfg, h, positions=positions,
                                 ragged=start is not None, qkv=(q, k, v))
        return y, ck, cv

    x, filled = _scan_cached_layers(params, cfg, x, cache, attend)
    new_cache = {"k": filled["k"], "v": filled["v"],
                 "length": cache["length"] + s}
    if start is not None:
        new_cache["start"] = start
    return x, new_cache


def prefill_packed(params: Dict, cfg: ModelConfig, tokens: jax.Array,
                   seg_ids: jax.Array, positions: jax.Array,
                   rows: jax.Array, cols: jax.Array, cache: Dict
                   ) -> Tuple[jax.Array, Dict]:
    """Padding-free packed prefill (DESIGN.md §12): the ragged batch's
    tokens ride concatenated in ``tokens [1, Tp]`` (Tp = bucketed total),
    with per-token metadata instead of a [B, T_max] grid —

      seg_ids   [Tp]    owning request per packed position (non-decreasing;
                        padding carries a larger sentinel)
      positions [1, Tp] logical position within the owning request (RoPE +
                        block-diagonal-causal masking)
      rows/cols [Tp]    KV scatter address per token: (batch row, slot) for
                        a contiguous cache, (physical page, offset) for a
                        paged pool (written at every layer of the stack in
                        place). Padding rows carry an out-of-range row
                        sentinel and are DROPPED by the scatter — no pad
                        token ever lands in a cache.

    Returns (hidden [1, Tp, d], cache with K/V scattered in). Bookkeeping
    leaves (length / start / block_table) are untouched: the engine
    installs them when a request's prefill completes, which is what keeps
    half-prefilled rows invisible to the decode batch."""
    assert cfg.family in ("dense_lm", "moe_lm", "vlm_lm", "audio_lm"), cfg.family
    x = _embed_inputs(params, cfg, tokens)
    paged = "k_pages" in cache

    def attend(lp, h, ck, cv, l):
        q, k, v = attn._project_qkv(lp["attn"], cfg, h, positions)
        at = (l, rows, cols) if paged else (rows, cols)
        ck = ck.at[at].set(k[0].astype(ck.dtype), mode="drop")
        cv = cv.at[at].set(v[0].astype(cv.dtype), mode="drop")
        y = attn.packed_attention_apply(lp["attn"], cfg, h, seg_ids,
                                        positions, qkv=(q, k, v))
        return y, ck, cv

    return _scan_cached_layers(params, cfg, x, cache, attend)


def prefill_continue(params: Dict, cfg: ModelConfig, tokens: jax.Array,
                     positions: jax.Array, rows: jax.Array, cols: jax.Array,
                     kv_sel: jax.Array, cache: Dict
                     ) -> Tuple[jax.Array, Dict]:
    """Chunked-prefill continuation for ONE request (DESIGN.md §12):
    ``tokens [1, C]`` is the next chunk of a long prompt whose earlier
    chunks already sit in the cache; ``positions [1, C]`` its absolute
    positions (``offset .. offset+C-1`` — packed-admitted rows have no
    left-pad, so logical == absolute). rows/cols address the K/V scatter
    exactly as in `prefill_packed`. ``kv_sel`` selects the row's cache for
    attention: the slot index (contiguous) or the [n_log] block-table row
    (paged). The chunk attends its own fresh keys plus every earlier slot
    through the causal mask — never another row's."""
    assert cfg.family in ("dense_lm", "moe_lm", "vlm_lm", "audio_lm"), cfg.family
    x = _embed_inputs(params, cfg, tokens)
    offset = positions[0, 0]
    paged = "k_pages" in cache
    if paged:
        from repro.kernels.attn.ref import gather_pages

    def attend(lp, h, ck, cv, l):
        q, k, v = attn._project_qkv(lp["attn"], cfg, h, positions)
        at = (l, rows, cols) if paged else (rows, cols)
        ck = ck.at[at].set(k[0].astype(ck.dtype), mode="drop")
        cv = cv.at[at].set(v[0].astype(cv.dtype), mode="drop")
        if paged:       # the row's pages of layer l, in one gather each
            krow = gather_pages(ck, kv_sel[None], layer=l)  # [1, S, Hkv, D]
            vrow = gather_pages(cv, kv_sel[None], layer=l)
        else:
            krow = jax.lax.dynamic_slice_in_dim(ck, kv_sel, 1, axis=0)
            vrow = jax.lax.dynamic_slice_in_dim(cv, kv_sel, 1, axis=0)
        y = attn.chunk_attention_apply(lp["attn"], cfg, q, krow, vrow,
                                       offset)
        return y, ck, cv

    return _scan_cached_layers(params, cfg, x, cache, attend)


def _zamba2_prefill(params, cfg: ModelConfig, x: jax.Array, cache: Dict
                    ) -> Tuple[jax.Array, Dict]:
    """Full-context zamba2 forward that also fills the hybrid cache:
    per-layer Mamba2 (ssd, conv) final states + ring-buffered shared-attn
    K/V for the last `win` positions."""
    b, s, _ = x.shape
    period = cfg.ssm.shared_period
    L = cfg.num_layers
    sb = params["shared_block"]
    win = cache["shared_k"].shape[2]
    scfg = cfg.replace(family="dense_lm")

    def mamba_body(xx, lp):
        lp = _unpack_layer(lp, cfg)
        h = norm_apply(cfg.norm, lp["ln"], xx)
        y, (ssd, conv) = m2.mamba2_apply(lp["mamba"], cfg, h)
        return xx + y, (ssd, conv)

    bounds = list(range(0, L, period)) + [L]
    ssd_parts, conv_parts, sk_parts, sv_parts = [], [], [], []
    # ring slots of the last `win` absolute positions
    tail = min(win, s)
    slots = (jnp.arange(s - tail, s)) % win
    for gi in range(len(bounds) - 1):
        g0, g1 = bounds[gi], bounds[gi + 1]
        group = jax.tree_util.tree_map(lambda a: a[g0:g1], params["layers"])
        x, (ssd_g, conv_g) = jax.lax.scan(mamba_body, x, group)
        ssd_parts.append(ssd_g)
        conv_parts.append(conv_g)
        if g1 < L or gi == len(bounds) - 2:
            h = norm_apply(cfg.norm, sb["ln_attn"], x)
            _, k, v = attn._project_qkv(sb["attn"], scfg, h,
                                        jnp.arange(s)[None, :])
            nk = cache["shared_k"][gi].at[:, slots].set(
                k[:, s - tail:].astype(cache["shared_k"].dtype))
            nv = cache["shared_v"][gi].at[:, slots].set(
                v[:, s - tail:].astype(cache["shared_v"].dtype))
            sk_parts.append(nk)
            sv_parts.append(nv)
            y = attn.attention_apply(sb["attn"], scfg, h,
                                     window_override=win)
            x = x + y
            h = norm_apply(cfg.norm, sb["ln_mlp"], x)
            x = x + mlp_apply(sb["mlp"], scfg, h)
    x = norm_apply(cfg.norm, params["final_norm"], x)
    new_cache = {
        "ssd": jnp.concatenate(ssd_parts, 0),
        "conv": jnp.concatenate(conv_parts, 0).astype(cache["conv"].dtype),
        "shared_k": jnp.stack(sk_parts, 0),
        "shared_v": jnp.stack(sv_parts, 0),
        "length": cache["length"] + s,
    }
    return x, new_cache
