"""Serving engine: batched prefill + decode over any assigned architecture.

Weights may be DBB-packed (`core.dbb_linear.pack_tree`). Under the Pallas
route (`ModelConfig.gemm_impl = "pallas"`, single device) the stacked layer
weights stay compressed **end-to-end**: the scan body hands the DbbWeight
leaves straight to the DBB kernels, which stream values+bitmask through
their K loop and decompress tiles in VMEM — no per-layer transient dense
copy, HBM residency is the compressed 62.5% for the whole decode step
(DESIGN.md §9). Decode-shaped GEMMs (M ≤ 32) dispatch to the skinny
weight-streaming kernels automatically. On the XLA route (distributed
graphs, CPU dry-run) packed layers expand transiently per layer inside the
scan body as before. Non-layer leaves (embedding table, LM head) are small
and read on *every* decode step, so `ServeEngine` expands them once up
front (`_decompress_non_layer` stays in the step functions for callers
that pass raw packed trees — it no-ops on pre-expanded params).

`ServeEngine.generate` runs static batches with **chunked token fetch**:
generated tokens and the per-row done mask live on device and cross to the
host once per `fetch_chunk` decode steps (a single scalar sync per chunk),
not once per token. `ServeEngine.serve` is the **continuous-batching**
scheduler on top of the same decode step: requests are admitted into free
slots between decode chunks (per-slot prefill scattered into the shared
cache), finished rows retire immediately, and every request decodes
token-identically to running solo (per-row lengths/start offsets,
DESIGN.md §5/§9). With ``cfg.kv_page_size > 0`` serve() switches to the
**paged KV cache** (DESIGN.md §10): KV lives in a fixed-size page pool,
requests admit with the pages they actually use (first-fit over the
queue) instead of reserving ``smax`` slots each, and decode runs the
block-table flash kernel — bit-identical tokens to the contiguous cache
at far higher occupancy per HBM byte.

`make_decode_step` / `make_prefill_step` produce the exact functions the
multi-pod dry-run lowers for the ``decode_*`` / ``prefill_*`` / ``long_*``
input-shape cells.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.core.dbb_linear import maybe_decompress_tree
from repro.dist.collectives import cross_entropy  # noqa: F401 (API surface)
from repro.dist.mesh_ctx import current_mesh, shard_tp, shard_tp_ctx
from repro.kernels import dispatch
from repro.models import registry

__all__ = ["make_decode_step", "make_prefill_step",
           "make_packed_prefill_step", "make_chunk_prefill_step",
           "make_sample_decode_step", "make_spec_decode_step",
           "make_sample_prefill_step", "make_sample_packed_prefill_step",
           "make_sample_chunk_prefill_step",
           "make_packed_logits_step", "make_decode_chunk",
           "make_sample_chunk", "ServeEngine", "greedy_from_hidden",
           "tp_serve_reason"]

# Families whose decode cache is the attention [L, B, S, H, D] K/V layout
# with per-row lengths — the continuous-batching scheduler scatters per-slot
# prefills into it. SSM/hybrid states are admitted wave-wise instead.
_CONT_BATCH_FAMILIES = ("dense_lm", "moe_lm", "vlm_lm", "audio_lm")


def greedy_from_hidden(hidden: jax.Array, w_head: jax.Array,
                       impl: str = "xla",
                       cfg: Optional[ModelConfig] = None) -> jax.Array:
    """hidden [B, 1, d] → greedy next token [B]. The [B, V] logits are tiny
    (one position); vocab stays sharded under GSPMD. impl="pallas" hands
    the head GEMV to the dispatch registry with the ``gemv`` hint
    (DESIGN.md §11): the skinny weight-streaming STA kernel when the batch
    fits the decode regime (B ≤ 32, §9), the XLA matmul otherwise — a
    [B, d]·[d, V] GEMV gains nothing from the M-tiled kernel's padding,
    which is exactly what the hint tells the `sta` route guard.

    Inside a TP shard_map body (the serving wrapper, DESIGN.md §14) the
    head arrives vocab-column-sharded [d, V/tp]: the local GEMV runs on
    the shard's vocab slice and a max/argmax all-gather of [B]-sized
    scalars — not [B, V] logits — picks the global winner."""
    h = hidden[:, -1].astype(jnp.float32)
    if shard_tp() > 1:
        from repro.dist.collectives import shard_greedy
        return shard_greedy(h, w_head, impl=impl, cfg=cfg)
    logits = dispatch.matmul(h, w_head.astype(jnp.float32), cfg=cfg,
                             pallas=(impl == "pallas"), gemv=True)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _gemm_impl(cfg: ModelConfig) -> str:
    """Resolve the engine's GEMM route (single predicate shared with the
    model layer: Pallas without a live mesh, or per-shard inside the TP
    shard_map wrapper)."""
    return "pallas" if dispatch.pallas_route_active(cfg) else "xla"


def tp_serve_reason(cfg: ModelConfig, mesh=None, params: Any = None) -> str:
    """Why the TP shard_map serving wrap is NOT active (empty = it is).

    The wrap (DESIGN.md §14) runs every step function's body per-shard —
    column-parallel QKV/up-projections, row-parallel o_proj/wo with one
    boundary all-reduce each, KV heads sharded over the cache — so it only
    engages when every axis it splits actually divides. With `params` the
    inferred specs are verified too (`tp_spec_violations`): a weight the
    divisibility fallback replicated would be reduce-summed tp× inside the
    body, so any gap keeps the wrap off. The returned string names the
    real rejection; dispatch.explain prints it alongside the mesh shape."""
    mesh = current_mesh() if mesh is None else mesh
    if mesh is None or "model" not in mesh.axis_names \
            or mesh.shape["model"] <= 1:
        return "no live mesh with a model axis > 1"
    tp = mesh.shape["model"]
    if cfg.gemm_impl != "pallas":
        return (f"gemm_impl={cfg.gemm_impl!r} — the wrap exists to put the "
                "Pallas kernels on per-shard shapes; XLA serving stays on "
                "the GSPMD graph")
    if cfg.parallel == "dp":
        return 'parallel="dp": the model axis carries ZeRO, not TP'
    if cfg.family not in _CONT_BATCH_FAMILIES or cfg.family == "moe_lm":
        return (f"family {cfg.family!r}: MoE expert dispatch / SSM state "
                "keep their own sharding (no generic KV-head split)")
    if cfg.num_heads % tp or cfg.num_kv_heads % tp:
        return (f"heads do not divide the model axis: num_heads="
                f"{cfg.num_heads}, num_kv_heads={cfg.num_kv_heads}, "
                f"tp={tp}")
    if cfg.d_ff % tp:
        return f"d_ff={cfg.d_ff} % tp={tp} != 0 (column-parallel MLP split)"
    if cfg.vocab_size % tp:
        return (f"vocab_size={cfg.vocab_size} % tp={tp} != 0 "
                "(vocab-parallel embed/head split)")
    if params is not None:
        from repro.dist.sharding import param_specs, tp_spec_violations
        gaps = tp_spec_violations(
            params, param_specs(params, mesh, cfg,
                                fsdp_min_shard_elems=None))
        if gaps:
            return ("weight leaves fall back to replication under the TP "
                    "specs (packed K-planes must split on DBB block "
                    "boundaries): " + ", ".join(gaps[:4])
                    + ("..." if len(gaps) > 4 else ""))
    return ""


def _decompress_non_layer(params, cfg: ModelConfig):
    """Expand packed leaves OUTSIDE the layer stack only. The stacked layer
    weights stay packed and either stream compressed through the DBB
    kernels (Pallas route, DESIGN.md §9) or expand per-layer *inside* the
    scan body (XLA route) — HBM never holds a whole-model dense copy
    (§Perf iteration 17)."""
    dt = jnp.dtype(cfg.dtype)
    if not isinstance(params, dict) or "layers" not in params:
        return maybe_decompress_tree(params, dtype=dt)
    out = {k: (v if k == "layers" else maybe_decompress_tree(v, dtype=dt))
           for k, v in params.items()}
    return out


def make_decode_step(cfg: ModelConfig):
    """decode_step(params, cache, tokens [B]) -> (next_tokens [B], cache)."""

    def step(params, cache, tokens):
        p = _decompress_non_layer(params, cfg)
        hidden, new_cache = registry.decode_step(p, cfg, tokens, cache)
        nxt = greedy_from_hidden(hidden, registry.lm_head_weight(p, cfg),
                                 impl=_gemm_impl(cfg), cfg=cfg)
        return nxt, new_cache

    return step


def make_prefill_step(cfg: ModelConfig):
    """prefill(params, cache, batch) -> (first generated token [B], cache).

    batch may carry ``start`` [B] — per-request left-pad counts for ragged
    batches; attention archs thread it through positions/masking and stash
    it in the cache for the decode steps (DESIGN.md §5)."""

    def step(params, cache, batch):
        p = _decompress_non_layer(params, cfg)
        hidden, new_cache = registry.prefill(
            p, cfg,
            tokens=batch.get("tokens"),
            embeds=batch.get("embeds"),
            prefix_embeds=batch.get("prefix_embeds"),
            cache=cache,
            start=batch.get("start"))
        nxt = greedy_from_hidden(hidden[:, -1:],
                                 registry.lm_head_weight(p, cfg),
                                 impl=_gemm_impl(cfg), cfg=cfg)
        return nxt, new_cache

    return step


def make_packed_prefill_step(cfg: ModelConfig):
    """packed_prefill(params, cache, tokens [1, Tp], seg_ids [Tp],
    positions [1, Tp], rows [Tp], cols [Tp], gather_idx [Gp])
    -> (next tokens [Gp], cache).

    One call prefills EVERY request packed into the token axis (DESIGN.md
    §12): K/V scatter to (rows, cols) — padding carries an out-of-range
    row and is dropped — and ``gather_idx`` names each request's last
    packed position, whose hidden state feeds the greedy head."""

    @jax.named_scope("prefill_packed")
    def step(params, cache, tokens, seg_ids, positions, rows, cols,
             gather_idx):
        p = _decompress_non_layer(params, cfg)
        hidden, new_cache = registry.prefill_packed(
            p, cfg, tokens, seg_ids, positions, rows, cols, cache)
        last = jnp.take(hidden[0], gather_idx, axis=0)[:, None]  # [Gp, 1, d]
        nxt = greedy_from_hidden(last, registry.lm_head_weight(p, cfg),
                                 impl=_gemm_impl(cfg), cfg=cfg)
        return nxt, new_cache

    return step


def make_packed_logits_step(cfg: ModelConfig):
    """packed_logits(params, cache, <make_packed_prefill_step args>) ->
    (last-position logits [Gp, vocab] f32, cache): the packed prefill step
    with its head left as logits — the correctness probe that compares
    routes on the serving path itself. Inside a TP shard_map body the
    vocab-parallel head's [Gp, vocab/tp] slices are all-gathered."""

    def step(params, cache, tokens, seg_ids, positions, rows, cols,
             gather_idx):
        p = _decompress_non_layer(params, cfg)
        hidden, new_cache = registry.prefill_packed(
            p, cfg, tokens, seg_ids, positions, rows, cols, cache)
        last = jnp.take(hidden[0], gather_idx, axis=0).astype(jnp.float32)
        w_head = registry.lm_head_weight(p, cfg).astype(jnp.float32)
        logits = dispatch.matmul(last, w_head, cfg=cfg,
                                 pallas=_gemm_impl(cfg) == "pallas",
                                 gemv=True)
        if shard_tp() > 1:
            logits = jax.lax.all_gather(logits, "model", axis=1, tiled=True)
        return logits, new_cache

    return step


def make_chunk_prefill_step(cfg: ModelConfig):
    """chunk_prefill(params, cache, tokens [1, Cp], positions [1, Cp],
    rows [Cp], cols [Cp], kv_sel, last_idx) -> (next token [1], cache).

    One continuation chunk of a long prompt for ONE request (DESIGN.md
    §12): scatter the chunk's K/V, attend the row's cache (selected by
    ``kv_sel`` — slot index or block-table row), and return the greedy
    token from the chunk's last real position (only consumed when this
    chunk completes the prompt)."""

    @jax.named_scope("prefill_continue")
    def step(params, cache, tokens, positions, rows, cols, kv_sel,
             last_idx):
        p = _decompress_non_layer(params, cfg)
        hidden, new_cache = registry.prefill_continue(
            p, cfg, tokens, positions, rows, cols, kv_sel, cache)
        last = jnp.take(hidden, last_idx, axis=1)[:, None]       # [1, 1, d]
        nxt = greedy_from_hidden(last, registry.lm_head_weight(p, cfg),
                                 impl=_gemm_impl(cfg), cfg=cfg)
        return nxt, new_cache

    return step


def make_sample_decode_step(cfg: ModelConfig, use_tt: bool = False):
    """Sampled decode (DESIGN.md §15): ``step(params, cache, tokens [B],
    sstate) -> ((next_tokens [B], sstate), cache)``.

    The sampling twin of `make_decode_step`: the head runs the fused
    penalty→temperature→gumbel epilogue through the dispatch registry and
    the emitted token folds into the on-device history (counts scatter +
    RNG ordinal) — no host sync added to the chunk loop. ``use_tt`` is
    static: False traces no top-k/top-p code at all (and keeps the fused
    route eligible); True pins the head to the XLA sampler."""
    from repro.serve import sampling

    def step(params, cache, tokens, sstate):
        p = _decompress_non_layer(params, cfg)
        hidden, new_cache = registry.decode_step(p, cfg, tokens, cache)
        nxt = sampling.sample_from_hidden(
            hidden, registry.lm_head_weight(p, cfg), sstate,
            impl=_gemm_impl(cfg), cfg=cfg, use_tt=use_tt)
        return (nxt, sampling.record_tokens(sstate, nxt)), new_cache

    return step


def make_spec_decode_step(cfg: ModelConfig, draft_k: int,
                          draft_layers: int):
    """Self-speculative decode (DESIGN.md §15): ``step(params, cache,
    tokens [B], sstate) -> ((emit [B, k+1], n_emit [B], sstate), cache)``.

    One speculative step per call: the TRUNCATED model (first
    ``draft_layers`` of the stacked weights, same embed/head) drafts
    ``draft_k`` tokens autoregressively against the cache's first layers;
    the FULL model verifies all k+1 positions in
    one skinny-M batched forward (`registry.verify_step` — K/V written at
    the absolute slots, ``length`` untouched); the standard
    rejection-sampling rule accepts a prefix and resamples the first
    rejected position from the residual distribution. Acceptance-aware
    slot accounting: ``length`` advances by exactly ``n_emit``, so the
    rejected tokens' K/V writes sit above the attention mask and are
    overwritten by the next step — the paged cache's rejected writes land
    in still-granted pages of the same row, never another request's.

    Top-k/top-p are not supported here (the engine gates speculation off
    for such batches): the acceptance rule needs matched p/q
    distributions, and truncating both would still leave the draft
    sampling its tokens from a differently-truncated support."""
    from repro.serve import sampling
    nd = draft_layers
    assert 0 < nd < cfg.num_layers, (nd, cfg.num_layers)
    dcfg = cfg.replace(num_layers=nd)
    k = draft_k

    def head_logits(h2d, p):
        """[M, d] hidden rows → [M, V] FULL-vocab f32 logits (the accept
        rule needs whole distributions; under TP the per-shard GEMV
        all-gathers its vocab columns — [M, V] with M ≤ B·(k+1) skinny
        rows, not a decode-batch [B, V] per layer)."""
        w = registry.lm_head_weight(p, cfg).astype(jnp.float32)
        lg = dispatch.matmul(h2d.astype(jnp.float32), w, cfg=cfg,
                             pallas=(_gemm_impl(cfg) == "pallas"),
                             gemv=True)
        if shard_tp() > 1:
            lg = jax.lax.all_gather(lg, "model", axis=-1, tiled=True)
        return lg

    def step(params, cache, tokens, sstate):
        from repro.kernels.sample import sample_logits
        p = _decompress_non_layer(params, cfg)
        b = tokens.shape[0]
        s = sstate
        # -- draft: k autoregressive steps of the truncated model. A
        # contiguous cache hands it a throwaway first-nd-layers copy; the
        # paged pools are not sliced: the draft runs layers 0..nd-1 of the
        # real stacks and writes slots length..length+k-1 there, every one
        # of which verify rewrites, in every layer, before it reads them
        dparams = dict(p, layers=jax.tree_util.tree_map(
            lambda a: a[:nd], p["layers"]))
        dcache = {key: (v[:nd] if key in ("k", "v") else v)
                  for key, v in cache.items()}
        cur = tokens
        d_toks, d_lgs = [], []
        for i in range(k):
            hidden, dcache = registry.decode_step(dparams, dcfg, cur,
                                                  dcache)
            lg = head_logits(hidden[:, -1], p)
            # counts snapshotted across the step (sampling/ops.py doc);
            # ordinal step+i matches the non-spec stream's draw counter
            tok = sample_logits(lg, s["counts"], s["temp"], s["top_k"],
                                s["top_p"], s["rep"], s["pres"], s["freq"],
                                s["seed"], s["step"] + i)
            d_toks.append(tok)
            d_lgs.append(lg)
            cur = tok
        draft_tok = jnp.stack(d_toks, axis=1)            # [B, k]
        draft_lg = jnp.stack(d_lgs, axis=1)              # [B, k, V]
        # -- verify: one skinny-M forward of the FULL model over
        # [cur, d_0..d_{k-1}]; writes K/V at slots length..length+k in
        # every layer, leaves cache["length"] untouched
        vt = jnp.concatenate([tokens[:, None], draft_tok], axis=1)
        if "k_pages" in cache:
            cache = dict(cache, k_pages=dcache["k_pages"],
                         v_pages=dcache["v_pages"])
        hidden, vcache = registry.verify_step(p, cfg, vt, cache)
        vlg = head_logits(hidden.reshape(b * (k + 1), -1), p)
        vlg = vlg.reshape(b, k + 1, -1)                  # [B, k+1, V]
        emit, n_emit = sampling.speculative_accept_state(
            draft_tok, draft_lg, vlg, s)
        # acceptance-aware slot accounting: exactly the accepted prefix +
        # cur become resident (the new cur = emit[n_emit-1] is NOT yet
        # written — same invariant as plain decode); rejected tokens'
        # writes sit at kpos >= length and are re-written next step
        new_cache = dict(vcache, length=cache["length"] + n_emit)
        return (emit, n_emit,
                sampling.record_emitted(s, emit, n_emit)), new_cache

    return step


def make_sample_prefill_step(cfg: ModelConfig, use_tt: bool = False):
    """Sampled prefill: ``step(params, cache, batch, fvals [G, 5],
    ivals [G, 2]) -> ((first token [G], sstate [G-row]), cache)``.

    The knob arrays are `pack_params` rows; a fresh request has zero
    output history, so the step builds a zero-counts state, samples the
    first token at RNG ordinal 0, and returns the state with that token
    already recorded (counts + ordinal advanced to 1)."""
    from repro.serve import sampling

    def step(params, cache, batch, fvals, ivals):
        p = _decompress_non_layer(params, cfg)
        hidden, new_cache = registry.prefill(
            p, cfg,
            tokens=batch.get("tokens"),
            embeds=batch.get("embeds"),
            prefix_embeds=batch.get("prefix_embeds"),
            cache=cache,
            start=batch.get("start"))
        w = registry.lm_head_weight(p, cfg)
        vocab = w.shape[-1] * max(1, shard_tp())
        state = sampling.fresh_state(fvals, ivals, vocab)
        nxt = sampling.sample_from_hidden(hidden[:, -1:], w, state,
                                          impl=_gemm_impl(cfg), cfg=cfg,
                                          use_tt=use_tt)
        return (nxt, sampling.record_tokens(state, nxt)), new_cache

    return step


def make_sample_packed_prefill_step(cfg: ModelConfig,
                                    use_tt: bool = False):
    """Sampled twin of `make_packed_prefill_step` (+ ``fvals [Gp, 5]`` /
    ``ivals [Gp, 2]`` in packed item order) → ``((tokens [Gp], sstate),
    cache)``. Spare gather rows carry zero knobs — temperature 0 over a
    zero history is a plain argmax, and their tokens are never consumed."""
    from repro.serve import sampling

    @jax.named_scope("prefill_packed")
    def step(params, cache, tokens, seg_ids, positions, rows, cols,
             gather_idx, fvals, ivals):
        p = _decompress_non_layer(params, cfg)
        hidden, new_cache = registry.prefill_packed(
            p, cfg, tokens, seg_ids, positions, rows, cols, cache)
        last = jnp.take(hidden[0], gather_idx, axis=0)[:, None]
        w = registry.lm_head_weight(p, cfg)
        vocab = w.shape[-1] * max(1, shard_tp())
        state = sampling.fresh_state(fvals, ivals, vocab)
        nxt = sampling.sample_from_hidden(last, w, state,
                                          impl=_gemm_impl(cfg), cfg=cfg,
                                          use_tt=use_tt)
        return (nxt, sampling.record_tokens(state, nxt)), new_cache

    return step


def make_sample_chunk_prefill_step(cfg: ModelConfig,
                                   use_tt: bool = False):
    """Sampled twin of `make_chunk_prefill_step` (+ ``fvals [1, 5]`` /
    ``ivals [1, 2]``) → ``((token [1], sstate), cache)``. The token is
    only consumed when the chunk completes the prompt — it is that
    request's FIRST emitted token, drawn at RNG ordinal 0."""
    from repro.serve import sampling

    @jax.named_scope("prefill_continue")
    def step(params, cache, tokens, positions, rows, cols, kv_sel,
             last_idx, fvals, ivals):
        p = _decompress_non_layer(params, cfg)
        hidden, new_cache = registry.prefill_continue(
            p, cfg, tokens, positions, rows, cols, kv_sel, cache)
        last = jnp.take(hidden, last_idx, axis=1)[:, None]
        w = registry.lm_head_weight(p, cfg)
        vocab = w.shape[-1] * max(1, shard_tp())
        state = sampling.fresh_state(fvals, ivals, vocab)
        nxt = sampling.sample_from_hidden(last, w, state,
                                          impl=_gemm_impl(cfg), cfg=cfg,
                                          use_tt=use_tt)
        return (nxt, sampling.record_tokens(state, nxt)), new_cache

    return step


def _chunk_loop(live, carry, steps: int, repeat):
    """Up to ``steps`` decode steps as one while loop that stops once every
    row is done (``carry[2]``, the done mask). ``live(carry) -> (carry,
    out)`` is one step; each leaf of ``out`` lands in a preallocated
    [steps, ...] block, and a step the loop skipped holds ``repeat(carry)``
    of the final carry (the last token again — never consumed: a done
    row's token loop already broke at its EOS). Returns (carry, outs).

    A while loop and not ``scan(cond(all_done, skip, live))``: a cond
    whose skip branch passes the carry through makes XLA copy the carry,
    the whole K/V pool, on every step."""
    outs = jax.tree_util.tree_map(
        lambda o: jnp.zeros((steps,) + o.shape, o.dtype), repeat(carry))

    def cond(st):
        i, carry, _ = st
        return (i < steps) & ~jnp.all(carry[2])

    def body(st):
        i, carry, outs = st
        carry, out = live(carry)
        return i + 1, carry, jax.tree_util.tree_map(
            lambda blk, o: blk.at[i].set(o), outs, out)

    n, carry, outs = jax.lax.while_loop(cond, body,
                                        (jnp.int32(0), carry, outs))
    ran = jnp.arange(steps) < n
    outs = jax.tree_util.tree_map(
        lambda blk, r: jnp.where(ran.reshape((steps,) + (1,) * r.ndim),
                                 blk, r[None]), outs, repeat(carry))
    return carry, outs


def make_decode_chunk(raw, eos_id: int, steps: int):
    """Greedy decode chunk: ``chunk(params, cache, cur [B], done [B]) ->
    (cur, cache, done, tokens [steps, B])``, up to ``steps`` steps of
    ``raw`` (a `make_decode_step` step) on device — ONE host fetch and ONE
    all-done sync per chunk instead of per token. Once every row is done
    the remaining steps are skipped (`_chunk_loop`)."""

    @jax.named_scope("decode_chunk")
    def chunk(params, cache, cur, done):
        def live(carry):
            cur, cache, done = carry
            nxt, cache = raw(params, cache, cur)
            return (nxt, cache, done | (nxt == eos_id)), nxt

        (cur, cache, done), toks = _chunk_loop(
            live, (cur, cache, done), steps, lambda c: c[0])
        return cur, cache, done, toks

    return chunk


def make_sample_chunk(raw, eos_id: int, steps: int, draft_k: int):
    """Sampled twin of `make_decode_chunk` over ``raw`` (a
    `make_sample_decode_step` step, or `make_spec_decode_step` when
    ``draft_k > 0``): ``chunk(params, cache, cur, done, sstate) -> (cur,
    cache, done, sstate, emit [steps, B, ke], n_emit [steps, B])`` with
    ``ke = draft_k + 1`` — the host drains a variable number of real
    tokens per step (`_consume_slot`). Same all-done early exit."""
    ke, spec = draft_k + 1, draft_k > 0

    @jax.named_scope("decode_chunk")
    def chunk(params, cache, cur, done, sstate):
        def live(carry):
            cur, cache, done, sstate = carry
            if spec:
                (emit, nem, sstate), cache = raw(params, cache, cur, sstate)
                mask = jnp.arange(ke)[None, :] < nem[:, None]
                done = done | jnp.any((emit == eos_id) & mask, axis=1)
                cur = jnp.take_along_axis(emit, (nem - 1)[:, None],
                                          axis=1)[:, 0]
            else:
                (cur, sstate), cache = raw(params, cache, cur, sstate)
                emit = cur[:, None]
                nem = jnp.ones(cur.shape, jnp.int32)
                done = done | (cur == eos_id)
            return (cur, cache, done, sstate), (emit, nem)

        def repeat(carry):
            cur = carry[0]
            return (jnp.broadcast_to(cur[:, None], (cur.shape[0], ke)),
                    jnp.ones(cur.shape, jnp.int32))

        (cur, cache, done, sstate), (emit, nem) = _chunk_loop(
            live, (cur, cache, done, sstate), steps, repeat)
        return cur, cache, done, sstate, emit, nem

    return chunk


def _consume_slot(host_emit: np.ndarray, host_nem: np.ndarray, slot: int,
                  row: List[int], left: int, eos_id: int
                  ) -> Tuple[int, bool, int]:
    """Drain one slot's emitted tokens from a fetched chunk into ``row``.

    ``host_emit`` [steps, B, ke] / ``host_nem`` [steps, B]: per decode
    step, the first ``host_nem[s, slot]`` entries of
    ``host_emit[s, slot]`` are real (speculative steps emit a variable
    1..k+1; plain steps always 1). Consumption stops at EOS or when the
    request's remaining ``left`` budget hits zero — surplus tokens from
    overshoot steps are discarded, exactly like the greedy loops.
    Returns (remaining budget, finished, steps the row consumed tokens
    from); the chunk's later steps are the row's surplus."""
    for s in range(host_emit.shape[0]):
        for j in range(int(host_nem[s, slot])):
            t = int(host_emit[s, slot, j])
            row.append(t)
            left -= 1
            if t == eos_id or left <= 0:
                return left, True, s + 1
    return left, False, host_emit.shape[0]


def _bump_spec_stats(stats: Dict[str, int], host_n: np.ndarray,
                     active: Dict[int, int]) -> None:
    """Accumulate speculative accounting over a chunk's live slots:
    tokens emitted vs speculative steps run (acceptance rate falls out as
    ``(spec_emitted / spec_steps - 1) / draft_k``). Overshoot steps of
    rows retiring mid-chunk are included — a slight undercount of the
    true acceptance, fine for the serve-stats gauge."""
    stats["spec_steps"] = (stats.get("spec_steps", 0)
                           + host_n.shape[0] * len(active))
    stats["spec_emitted"] = (stats.get("spec_emitted", 0)
                             + sum(int(host_n[:, s].sum())
                                   for s in active))


@dataclasses.dataclass
class _ServeCall:
    """State of one ``serve()`` call that both scheduler loops share: the
    device-resident decode batch (cache, current tokens, done mask,
    sampling state), the slot bookkeeping, the counters that become
    ``serve_stats``, and each request's timeline in seconds from ``t0``,
    the loop's start: slot assigned (``assign_s``), first token on the
    host (``ttft_s``), last token consumed (``done_s``)."""
    t0: float
    cache: Any
    cur: jax.Array
    done: jax.Array
    sstate: Any                  # None unless the call samples
    outs: List[List[int]]
    free: List[int]              # free slots
    stats: Dict[str, Any]
    active: Dict[int, int] = dataclasses.field(
        default_factory=dict)    # slot -> request idx
    left: Dict[int, int] = dataclasses.field(
        default_factory=dict)    # request idx -> budget
    assign_s: Dict[int, float] = dataclasses.field(default_factory=dict)
    ttft_s: Dict[int, float] = dataclasses.field(default_factory=dict)
    done_s: Dict[int, float] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        self.stats.update(decode_steps=0, decode_row_steps=0,
                          decode_surplus_row_steps=0)

    def stamp(self, times: Dict[int, float], ridx: int) -> None:
        times[ridx] = time.perf_counter() - self.t0

    def finish(self) -> Dict[str, Any]:
        """``stats`` with the timeline, one entry per request (nan where a
        request never got that far)."""
        for key in ("assign_s", "ttft_s", "done_s"):
            times = getattr(self, key)
            self.stats[key] = [times.get(i, float("nan"))
                               for i in range(len(self.outs))]
        return self.stats


def _bucket_len(n: int, minimum: int = 8) -> int:
    """Pad a prompt length up to a power-of-two bucket (≥ minimum) so the
    per-slot admission prefill compiles once per bucket, not once per
    prompt length. Left-pad + ``start`` offsets make the padding exact
    (DESIGN.md §5)."""
    b = max(minimum, 1)
    while b < n:
        b *= 2
    return b


def _packed_call_args(seqs: Sequence[Sequence[int]], addrs, pad_row: int
                      ) -> Tuple[Tuple[jax.Array, ...], int]:
    """Device args of one packed cu_seqlens prefill call (DESIGN.md §12):
    ``seqs[i]`` are request i's tokens and ``addrs[i]`` their (rows, cols)
    KV scatter addresses. Returns ((tokens [1, Tp], seg_ids [Tp],
    positions [1, Tp], rows [Tp], cols [Tp], gather_idx [Gp]), Tp):
    Tp/Gp are power-of-two bucketed, ``gather_idx`` names each request's
    last packed position, and padding carries segment id ``len(seqs)``
    (larger than every real id, so seg stays non-decreasing and matches
    nothing) and scatter row ``pad_row`` (out of range: dropped)."""
    tp = _bucket_len(sum(len(q) for q in seqs), 8)
    toks = np.zeros((tp,), np.int32)
    seg = np.full((tp,), len(seqs), np.int32)
    pos = np.zeros((tp,), np.int32)
    rows = np.full((tp,), pad_row, np.int32)
    cols = np.zeros((tp,), np.int32)
    gidx = np.zeros((_bucket_len(len(seqs), 1),), np.int32)
    off = 0
    for i, (q, (r, c)) in enumerate(zip(seqs, addrs)):
        n = len(q)
        toks[off:off + n] = q
        seg[off:off + n] = i
        pos[off:off + n] = np.arange(n)
        rows[off:off + n], cols[off:off + n] = r, c
        gidx[i] = off + n - 1
        off += n
    return (jnp.asarray(toks)[None], jnp.asarray(seg),
            jnp.asarray(pos)[None], jnp.asarray(rows), jnp.asarray(cols),
            jnp.asarray(gidx)), tp


@dataclasses.dataclass
class ServeEngine:
    """Batched greedy-decoding engine (examples + tests + benchmarks).

    Single-host. Two entry points:

    * `generate(prompts)` — one static batch (≤ `max_batch`): one prefill,
      then a decode loop. Generated tokens and the done mask stay ON
      DEVICE; the host syncs one scalar per `fetch_chunk` decode steps and
      pulls the token buffer when the batch finishes — no per-token
      device→host round-trip.
    * `serve(prompts)` — continuous batching over any number of requests:
      new requests are admitted into free slots between decode chunks (a
      single-row prefill scattered into the shared cache at the slot
      index), finished rows retire immediately and free their slot. A
      request admitted mid-stream decodes token-identically to running
      solo: per-row cache lengths, left-pad ``start`` offsets and RoPE
      positions isolate every row (DESIGN.md §5/§9).

    Ragged batches: prompts are left-padded and the per-row pad counts
    travel as ``start`` offsets — attention archs mask pad keys and shift
    RoPE positions so a short prompt in a mixed batch decodes
    token-identically to running it solo. SSM archs' recurrent state still
    consumes the pads (see `prefill`); they also fall back to wave-wise
    static batching under `serve`.

    Packed (DBB) weights outside the layer stack — embedding table, LM
    head — are decompressed ONCE at engine construction; the stacked layer
    weights stay compressed in HBM and, on the Pallas route, stream
    compressed through the DBB kernels for the whole decode step
    (DESIGN.md §9).
    """
    cfg: ModelConfig
    params: Any
    max_batch: int = 8
    eos_id: int = 1
    fetch_chunk: int = 8
    # paged KV (DESIGN.md §10): physical page pool size for serve() when
    # ``cfg.kv_page_size > 0``. 0 = parity with the contiguous cache's HBM
    # footprint (max_batch · n_log pages, + the reserved dummy); set it
    # explicitly to serve against a fixed HBM budget — admission then packs
    # as many requests as their *used* pages allow.
    kv_pool_pages: int = 0
    # None: serve() pages iff cfg.kv_page_size > 0. False pins the
    # contiguous scheduler while keeping kv_page_size as the flash decode
    # kernel's KV tile — the identity-block-table control the paged-vs-
    # contiguous bit-equivalence suite compares against.
    paged: Optional[bool] = None
    # prefill layout for serve() (DESIGN.md §12): "packed" concatenates
    # admitted prompts into one [total_tokens] axis (no pad token ever
    # enters a GEMM); "padded" is the legacy per-slot left-padded bucket
    # prefill the parity suite compares against.
    prefill_mode: str = "packed"
    # split prompts into fixed-size chunks so the scheduler interleaves
    # prefill with decode chunks (bounds decode-row TTFT jitter under
    # heavy admission). 0 = whole-prompt prefill. Packed mode only.
    prefill_chunk: int = 0
    # self-speculative decode (DESIGN.md §15): draft_k > 0 drafts that
    # many tokens per step with the truncated model and verifies them in
    # one batched forward. Only engages on sampled calls (generate/serve
    # with ``sampling=``); per-call ``draft_k=`` overrides. draft_layers
    # picks the truncation depth (0 = num_layers // 2).
    draft_k: int = 0
    draft_layers: int = 0

    def __post_init__(self):
        # the diagnostic int32 indices plane is host-side validation
        # material (validate_dbb) — 4 B/value of dead HBM on a serving
        # engine. Strip it from every device-resident packed leaf up
        # front; kernels and decompress consume the bitmask only.
        from repro.core.dbb import DbbWeight as _Dbb
        self.params = jax.tree_util.tree_map(
            lambda l: (dataclasses.replace(l, indices=None)
                       if isinstance(l, _Dbb) and l.indices is not None
                       else l),
            self.params, is_leaf=lambda l: isinstance(l, _Dbb))
        # hoisted non-layer decompression: pay the embed/LM-head DBB
        # expansion once here instead of on every decode step (the inner
        # _decompress_non_layer then no-ops — no packed non-layer leaves);
        # drop our reference to the packed originals so they don't reside
        # next to their dense copies for the engine's lifetime
        self.params = jax.jit(
            lambda p: _decompress_non_layer(p, self.cfg))(self.params)
        # TP serving wrap (DESIGN.md §14): with a live TP mesh and the
        # Pallas route requested, every step function's body runs per-shard
        # under one shard_map — params/KV sharded by the Megatron specs,
        # boundary collectives inside the body. tp_reason records why the
        # wrap is off (empty = on) for explain/diagnostics.
        mesh = current_mesh()
        self.tp_reason = tp_serve_reason(self.cfg, mesh, self.params)
        self._tp = 0 if self.tp_reason else mesh.shape["model"]
        self._mesh = None if self.tp_reason else mesh
        if self._tp:
            from repro.dist.sharding import (named_sharding_tree,
                                             param_specs)
            self._pspecs = param_specs(self.params, mesh, self.cfg,
                                       fsdp_min_shard_elems=None)
            self.params = jax.device_put(
                self.params, named_sharding_tree(self._pspecs, mesh))
        self._prefill = jax.jit(self._tp_step(make_prefill_step))
        self._decode_raw = self._tp_step(make_decode_step)
        self._decode = jax.jit(self._decode_raw, donate_argnums=1)
        self._chunk_fns: Dict[int, Any] = {}
        self._admit = jax.jit(self._admit_fn, donate_argnums=0)
        self._admit_paged = jax.jit(self._admit_paged_fn, donate_argnums=0)
        self._packed_prefill = jax.jit(self._tp_step(make_packed_prefill_step),
                                       donate_argnums=1)
        self._prefill_continue = jax.jit(self._tp_step(make_chunk_prefill_step),
                                         donate_argnums=1)
        self._install = jax.jit(self._install_fn, donate_argnums=0)
        self._install_paged = jax.jit(self._install_paged_fn,
                                      donate_argnums=0)
        self._logits = None          # prefill_logits' step, built on use
        # sampled/speculative variants, built lazily per static knob set
        # (use_tt, draft_k) — a greedy engine never traces sampling code
        self._sample_raws: Dict[Any, Any] = {}
        self._sample_chunks: Dict[Any, Any] = {}
        self._sample_prefills: Dict[Any, Any] = {}
        self._sstate_admit = jax.jit(self._sstate_admit_fn,
                                     donate_argnums=0)
        # filled by the paged serve() scheduler (occupancy benchmarking)
        self.serve_stats: Dict[str, int] = {}

    def _tp_step(self, maker):
        """Build one step function from its maker; when the TP wrap is
        active, shard_map it over the serving mesh (DESIGN.md §14).

        The body runs the step built with a *localized* cfg (heads ÷ tp,
        head_dim pinned so the ratio survives) inside `shard_tp_ctx`, which
        is what re-enables the Pallas route guards on per-shard shapes.
        Params shard by the Megatron TP specs; the KV cache shards its
        KV-heads dim (contiguous and paged layouts both carry it at dim 3,
        so paged block tables are per-shard: replicated tables indexing
        shard-local pools of local heads); token/bookkeeping args
        replicate. Cache specs are derived per call from the actual tree —
        generate/serve/paged caches differ in structure."""
        if not self._tp:
            return maker(self.cfg)
        tp, mesh, pspecs = self._tp, self._mesh, self._pspecs
        lcfg = self.cfg.replace(
            num_heads=self.cfg.num_heads // tp,
            num_kv_heads=self.cfg.num_kv_heads // tp,
            head_dim=self.cfg.resolved_head_dim)
        inner = maker(lcfg)

        def stepped(params, cache, *rest):
            from repro.dist.sharding import serve_cache_specs
            cspecs = serve_cache_specs(cache, mesh)

            def body(p, c, *r):
                with shard_tp_ctx(tp):
                    return inner(p, c, *r)

            return shard_map(
                body, mesh=mesh,
                in_specs=(pspecs, cspecs) + (P(),) * len(rest),
                out_specs=(P(), cspecs),
                check_vma=False)(params, cache, *rest)

        return stepped

    # -- decode chunks: N steps per host round-trip -----------------------

    def _chunk_fn(self, steps: int):
        """Jitted `make_decode_chunk` of `steps` decode steps, the cache
        donated.

        Callers always pass the engine's fixed `fetch_chunk` and discard
        surplus tokens host-side: each distinct `steps` compiles its own
        whole-model loop, and a variable tail size would turn the end of
        every request into a mid-serving XLA compile. (Overshoot decode
        steps write per-row clamped cache slots whose tokens are never
        consumed — see generate/serve.)"""
        fn = self._chunk_fns.get(steps)
        if fn is None:
            fn = jax.jit(make_decode_chunk(self._decode_raw, self.eos_id,
                                           steps), donate_argnums=1)
            self._chunk_fns[steps] = fn
        return fn

    # -- sampled / speculative variants (DESIGN.md §15) -------------------

    def _resolved_draft_layers(self) -> int:
        return self.draft_layers or max(1, self.cfg.num_layers // 2)

    def _sample_raw(self, use_tt: bool, draft_k: int):
        """`_tp_step`-wrapped sampled (or speculative) decode step, cached
        per static knob set."""
        key = (use_tt, draft_k)
        fn = self._sample_raws.get(key)
        if fn is None:
            if draft_k > 0:
                nd = self._resolved_draft_layers()
                fn = self._tp_step(
                    lambda c: make_spec_decode_step(c, draft_k, nd))
            else:
                fn = self._tp_step(
                    lambda c: make_sample_decode_step(c, use_tt))
            self._sample_raws[key] = fn
        return fn

    def _sample_prefill_fn(self, mode: str, use_tt: bool):
        """Jitted sampled prefill for ``mode`` in padded/packed/chunk."""
        key = (mode, use_tt)
        fn = self._sample_prefills.get(key)
        if fn is None:
            maker = {"padded": make_sample_prefill_step,
                     "packed": make_sample_packed_prefill_step,
                     "chunk": make_sample_chunk_prefill_step}[mode]
            stepped = self._tp_step(lambda c: maker(c, use_tt))
            # padded admission reuses a pristine cache template (never
            # donated); packed/chunk scatter into the live shared cache
            fn = (jax.jit(stepped) if mode == "padded"
                  else jax.jit(stepped, donate_argnums=1))
            self._sample_prefills[key] = fn
        return fn

    def _sample_chunk_fn(self, steps: int, use_tt: bool, draft_k: int):
        """Jitted `make_sample_chunk`, cached per static knob set; the
        cache and the sampling state are donated."""
        key = (steps, use_tt, draft_k)
        fn = self._sample_chunks.get(key)
        if fn is None:
            fn = jax.jit(make_sample_chunk(self._sample_raw(use_tt, draft_k),
                                           self.eos_id, steps, draft_k),
                         donate_argnums=(1, 4))
            self._sample_chunks[key] = fn
        return fn

    @staticmethod
    def _sstate_admit_fn(sstate, slot, fvals, ivals, tok):
        """Install one admitted request's sampling lanes at ``slot`` and
        fold its prefill-sampled first token into the fresh history
        (counts[slot, tok] = 1, RNG ordinal = 1 — matching what
        `record_tokens` did inside the prefill step's own G-row state)."""
        from repro.serve.sampling import state_install
        s = state_install(sstate, slot, fvals, ivals)
        return dict(s, counts=s["counts"].at[slot, tok].add(1),
                    step=s["step"].at[slot].set(1))

    # -- static batch -----------------------------------------------------

    def generate(self, prompts: List[List[int]], max_new_tokens: int = 16,
                 sampling: Optional[Sequence[Any]] = None,
                 draft_k: Optional[int] = None) -> List[List[int]]:
        assert len(prompts) <= self.max_batch
        if sampling is not None:
            return self._generate_sampled(prompts, max_new_tokens,
                                          sampling, draft_k)
        b = len(prompts)
        max_len = max(len(p) for p in prompts)
        total = max_len + max_new_tokens
        toks = np.zeros((self.max_batch, max_len), np.int32)
        start = np.zeros((self.max_batch,), np.int32)
        for i, p in enumerate(prompts):
            toks[i, max_len - len(p):] = p          # left-pad
            start[i] = max_len - len(p)
        cache = registry.init_cache(self.cfg, self.max_batch, total)
        batch = {"tokens": jnp.asarray(toks)}
        if start.any():
            # only genuinely ragged batches pay the per-row position/mask
            # machinery — an all-zero start would force every batched
            # prefill onto the naive [B,S] attention path for nothing
            batch["start"] = jnp.asarray(start)
            if self._tp:
                # the TP wrap derives shard_map out_specs from the INPUT
                # cache tree; ragged prefill adds the "start" leaf to the
                # returned cache, so seed it up front to keep the pytree
                # structures aligned
                cache["start"] = jnp.zeros((self.max_batch,), jnp.int32)
            if self.cfg.family in ("rwkv6", "zamba2"):
                import warnings
                warnings.warn(
                    f"{self.cfg.family}: ragged batch pads feed the "
                    "recurrent state — short prompts may decode "
                    "differently than solo (needs right-padding + state "
                    "masking; see transformer.prefill)", stacklevel=2)
        cur, cache = self._prefill(self.params, cache, batch)
        # device-side recording: pad rows start done, real rows check eos
        done = jnp.asarray(np.arange(self.max_batch) >= b) | (
            cur == self.eos_id)
        chunks = [cur[None]]                        # [1, B] on device
        remaining = max_new_tokens - 1
        while remaining > 0 and not bool(jnp.all(done)):
            # fixed-size chunks (one compiled scan); the tail overshoot's
            # tokens are trimmed below and its clamped cache writes only
            # ever feed further discarded tokens
            cur, cache, done, toks_d = self._chunk_fn(self.fetch_chunk)(
                self.params, cache, cur, done)
            chunks.append(toks_d)
            remaining -= self.fetch_chunk
        host = np.concatenate([np.asarray(c) for c in chunks], axis=0)
        outs: List[List[int]] = []
        for i in range(b):
            row: List[int] = []
            for t in host[:max_new_tokens, i]:
                row.append(int(t))
                if t == self.eos_id:
                    break
            outs.append(row)
        return outs

    def prefill_logits(self, prompts: List[List[int]]) -> np.ndarray:
        """Last-position logits [len(prompts), vocab] (f32, on the host)
        of each prompt through the packed prefill path `serve` admits
        with — same kernels, KV scatter and TP wrap, head argmax left
        off. Compare it across ``gemm_impl`` on the same weights to check
        a route end to end."""
        assert 0 < len(prompts) <= self.max_batch
        backend = _ContiguousKvBackend(
            self, _bucket_len(max(len(q) for q in prompts)))
        packed, _ = _packed_call_args(
            prompts,
            [backend.token_addr(i, (), np.arange(len(q), dtype=np.int64))
             for i, q in enumerate(prompts)],
            backend.pad_row())
        if self._logits is None:
            self._logits = jax.jit(self._tp_step(make_packed_logits_step))
        logits, _ = self._logits(self.params, backend.init_cache(), *packed)
        return np.asarray(logits[:len(prompts)])

    def _spec_mode(self, sampling: Sequence[Any],
                   draft_k: Optional[int]) -> Tuple[bool, int]:
        """Resolve a sampled call's static knobs: (use_tt, draft_k), with
        speculation gated OFF (warning, not error) when this config or
        batch cannot honor it."""
        import warnings

        from repro.serve.sampling import any_uses_tt
        use_tt = any_uses_tt(sampling)
        dk = self.draft_k if draft_k is None else draft_k
        if dk > 0:
            reason = ""
            if self.cfg.family not in _CONT_BATCH_FAMILIES:
                reason = (f"family {self.cfg.family!r} has no "
                          "slot-addressed K/V cache for batched verify")
            elif use_tt:
                reason = ("top-k/top-p requests in the batch — the "
                          "acceptance rule needs untruncated p/q")
            elif self.cfg.num_layers < 2:
                reason = "needs num_layers >= 2 to truncate a draft"
            if reason:
                warnings.warn(f"speculative decode disabled ({reason}) — "
                              "serving with plain sampling", stacklevel=3)
                dk = 0
        return use_tt, dk

    def _generate_sampled(self, prompts: List[List[int]],
                          max_new_tokens: int, sampling: Sequence[Any],
                          draft_k: Optional[int]) -> List[List[int]]:
        """Sampled/speculative twin of the static `generate` path. Same
        one-sync-per-chunk loop; chunks emit (emit, n_emit) blocks and the
        host drains a variable token count per step."""
        from repro.serve.sampling import pack_params
        b = len(prompts)
        assert len(sampling) == b, (len(sampling), b)
        use_tt, dk = self._spec_mode(sampling, draft_k)
        ke = dk + 1
        max_len = max(len(p) for p in prompts)
        # speculative verify writes a (k+1)-slab at the write cursor:
        # give the cache that margin past the budget so no in-budget
        # step's slab ever clamps into resident slots
        total = max_len + max_new_tokens + (ke if dk else 0)
        toks = np.zeros((self.max_batch, max_len), np.int32)
        start = np.zeros((self.max_batch,), np.int32)
        for i, p in enumerate(prompts):
            toks[i, max_len - len(p):] = p          # left-pad
            start[i] = max_len - len(p)
        fv = np.zeros((self.max_batch, 5), np.float32)
        fv[:, 1] = 1.0                               # top_p identity
        fv[:, 2] = 1.0                               # repetition identity
        iv = np.zeros((self.max_batch, 2), np.int32)
        for i, sp in enumerate(sampling):
            f, ivv = pack_params(sp)
            fv[i], iv[i] = np.asarray(f), np.asarray(ivv)
        cache = registry.init_cache(self.cfg, self.max_batch, total)
        batch = {"tokens": jnp.asarray(toks)}
        if start.any():
            batch["start"] = jnp.asarray(start)
            if self._tp:
                # keep shard_map in/out cache pytrees aligned (see
                # `generate`)
                cache["start"] = jnp.zeros((self.max_batch,), jnp.int32)
        (cur, sstate), cache = self._sample_prefill_fn("padded", use_tt)(
            self.params, cache, batch, jnp.asarray(fv), jnp.asarray(iv))
        done = jnp.asarray(np.arange(self.max_batch) >= b) | (
            cur == self.eos_id)
        first = np.zeros((1, self.max_batch, ke), np.int64)
        first[0, :, 0] = np.asarray(cur)
        he_list = [first]
        hn_list = [np.ones((1, self.max_batch), np.int64)]
        # per-row emitted counts steer the loop: speculative chunks emit
        # 1..k+1 per step, so "steps run" no longer measures progress
        got = np.ones((self.max_batch,), np.int64)
        while True:
            dh = np.asarray(done)
            if np.all(dh | (got >= max_new_tokens)):
                break
            cur, cache, done, sstate, e_d, n_d = self._sample_chunk_fn(
                self.fetch_chunk, use_tt, dk)(
                    self.params, cache, cur, done, sstate)
            he_list.append(np.asarray(e_d))
            hn = np.asarray(n_d)
            hn_list.append(hn)
            got += hn.sum(axis=0)
        host_e = np.concatenate(he_list, axis=0)
        host_n = np.concatenate(hn_list, axis=0)
        outs: List[List[int]] = []
        for i in range(b):
            row: List[int] = []
            _consume_slot(host_e, host_n, i, row, max_new_tokens,
                          self.eos_id)
            outs.append(row)
        return outs

    # -- continuous batching ----------------------------------------------

    @staticmethod
    def _admit_fn(cache, cache_one, cur, done, slot, tok):
        """Scatter a finished single-row prefill into the shared decode
        state at `slot` (traced index — one compilation serves every
        slot). Row-indexed leaves (length/start) write at [slot], stacked
        K/V leaves at [:, slot]."""
        new = {}
        for key, leaf in cache.items():
            if leaf.ndim == 1:                       # length / start
                new[key] = leaf.at[slot].set(cache_one[key][0])
            else:                                    # [L, B, S, H, D] K/V
                new[key] = leaf.at[:, slot].set(cache_one[key][:, 0])
        return new, cur.at[slot].set(tok), done.at[slot].set(False)

    @staticmethod
    def _admit_paged_fn(cache, cache_one, cur, done, table_row, slot, tok):
        """Paged admission (DESIGN.md §10): scatter the single-row
        contiguous prefill cache into the physical page pool at the pages
        named by ``table_row`` [n_log] and install the table row at
        ``slot``. Unallocated tail entries of the row point at the
        reserved dummy page — their scatter writes (and any later
        overshoot writes of this slot) land there harmlessly. Traced row /
        slot / token: one compilation serves every admission."""
        n_log = cache["block_table"].shape[1]
        page = cache["k_pages"].shape[2]
        k1 = cache_one["k"]                          # [L, 1, smax, H, D]
        L, _, smax, h, d = k1.shape
        kpg = k1.reshape(L, n_log, page, h, d)
        vpg = cache_one["v"].reshape(L, n_log, page, h, d)
        new = {
            "k_pages": cache["k_pages"].at[:, table_row].set(kpg),
            "v_pages": cache["v_pages"].at[:, table_row].set(vpg),
            "block_table": cache["block_table"].at[slot].set(table_row),
            "length": cache["length"].at[slot].set(cache_one["length"][0]),
            "start": cache["start"].at[slot].set(cache_one["start"][0]),
        }
        return new, cur.at[slot].set(tok), done.at[slot].set(False)

    @staticmethod
    def _install_fn(cache, cur, done, slot, tok, length):
        """Activate a slot whose prompt finished PACKED prefill: the K/V
        already sits in the shared cache (scattered token-by-token by the
        packed/chunk prefill calls), so activation only installs the
        bookkeeping — length, a zero start (packed rows have no left-pad),
        the first generated token, and the live done bit."""
        new = dict(cache,
                   length=cache["length"].at[slot].set(length),
                   start=cache["start"].at[slot].set(0))
        return new, cur.at[slot].set(tok), done.at[slot].set(False)

    @staticmethod
    def _install_paged_fn(cache, cur, done, table_row, slot, tok, length):
        """Paged activation: same as `_install_fn` plus the block-table
        row. Until this runs the slot's table points at the dummy page, so
        the half-prefilled pages (written physically, table-bypassing)
        were invisible to every decode step."""
        new = dict(cache,
                   block_table=cache["block_table"].at[slot].set(table_row),
                   length=cache["length"].at[slot].set(length),
                   start=cache["start"].at[slot].set(0))
        return new, cur.at[slot].set(tok), done.at[slot].set(False)

    def serve(self, prompts: List[List[int]],
              max_new_tokens: Union[int, Sequence[int]] = 16,
              fetch_chunk: Optional[int] = None,
              prompt_bucket: int = 8,
              prefill_mode: Optional[str] = None,
              prefill_chunk: Optional[int] = None,
              sampling: Optional[Sequence[Any]] = None,
              draft_k: Optional[int] = None) -> List[List[int]]:
        """Continuous-batching greedy decode over any number of requests.

        max_new_tokens: one budget for all requests, or one per request.
        Requests are admitted into free slots between decode chunks and
        retire the moment they hit EOS or their budget — the batch stays
        full whenever there is queued work, instead of draining to the
        slowest request like a static wave.

        prefill_mode / prefill_chunk override the engine defaults per
        call: "packed" (default) prefills admitted prompts padding-free
        through the cu_seqlens path, optionally split into
        ``prefill_chunk``-token chunks interleaved with decode chunks;
        "padded" is the legacy left-padded per-slot prefill (DESIGN.md
        §12)."""
        n_req = len(prompts)
        if isinstance(max_new_tokens, int):
            budgets = [max_new_tokens] * n_req
        else:
            budgets = list(max_new_tokens)
            assert len(budgets) == n_req, (len(budgets), n_req)
        if n_req == 0:
            return []
        if sampling is not None:
            assert len(sampling) == n_req, (len(sampling), n_req)
        if self.cfg.family not in _CONT_BATCH_FAMILIES:
            # SSM/hybrid states have no slot-scatterable K/V cache yet —
            # serve them as static waves (correct, just not continuous)
            import warnings
            warnings.warn(
                f"{self.cfg.family}: continuous batching needs the "
                "attention K/V cache layout — falling back to static "
                "waves", stacklevel=2)
            outs = []
            for i in range(0, n_req, self.max_batch):
                wave_p = prompts[i:i + self.max_batch]
                wave_b = budgets[i:i + self.max_batch]
                wave_s = (None if sampling is None
                          else sampling[i:i + self.max_batch])
                res = self.generate(wave_p, max_new_tokens=max(wave_b),
                                    sampling=wave_s, draft_k=draft_k)
                outs.extend(r[:bud] for r, bud in zip(res, wave_b))
            return outs

        use_tt, dk = (False, 0) if sampling is None else \
            self._spec_mode(sampling, draft_k)
        # speculative margin: verify writes a (k+1)-slab at the write
        # cursor, so every reservation (and smax) carries that headroom
        dmargin = dk + 1 if dk else 0
        chunk = fetch_chunk or self.fetch_chunk
        blens = [_bucket_len(len(p), prompt_bucket) for p in prompts]
        # bucket the cache length too: serve() calls with nearby budgets
        # must reuse one compiled chunk scan / admit scatter / prefill
        smax = _bucket_len(max(blens) + max(budgets) + dmargin,
                           prompt_bucket)
        if self.cfg.kv_page_size > 0:
            # page-align smax for BOTH schedulers: the contiguous flash
            # decode gate needs smax % page == 0, and a contiguous engine
            # on an unaligned smax would silently take the XLA softmax
            # path while the paged engine runs the kernel — breaking the
            # paged-vs-contiguous bit-identity contract (DESIGN.md §10)
            page = self.cfg.kv_page_size
            smax = -(-smax // page) * page
        use_paged = (self.cfg.kv_page_size > 0 if self.paged is None
                     else self.paged)
        if use_paged:
            reason = _paged_unsupported_reason(self.cfg, self._tp)
            if reason:
                # the paged branch decodes through the flash kernel
                # unconditionally — honor a config it cannot serve by
                # falling back to the contiguous scheduler instead of
                # silently overriding the user's backend choice
                import warnings
                warnings.warn(f"paged KV serving unavailable ({reason}) — "
                              "falling back to the contiguous scheduler",
                              stacklevel=2)
                use_paged = False
        backend = (_PagedKvBackend(self, smax) if use_paged
                   else _ContiguousKvBackend(self, smax))
        mode = prefill_mode if prefill_mode is not None else self.prefill_mode
        assert mode in ("packed", "padded"), mode
        with TraceAnnotation("serve.call", requests=n_req):
            if mode == "packed":
                pchunk = (prefill_chunk if prefill_chunk is not None
                          else self.prefill_chunk)
                return self._serve_loop_packed(prompts, budgets, blens,
                                               smax, chunk, backend, pchunk,
                                               sampling, use_tt, dk)
            return self._serve_loop(prompts, budgets, blens, smax, chunk,
                                    backend, sampling, use_tt, dk)

    def _serve_loop(self, prompts: List[List[int]], budgets: List[int],
                    blens: List[int], smax: int, chunk: int, backend,
                    sampling: Optional[Sequence[Any]] = None,
                    use_tt: bool = False, dk: int = 0) -> List[List[int]]:
        """The one continuous-batching scheduler both KV layouts share.
        The backend only decides how cache space is reserved and where
        admissions scatter (contiguous slots vs allocated pages) — token
        accounting, chunk decode, and retirement live once
        (`_decode_chunk`, shared with `_serve_loop_packed`), so the two
        layouts cannot drift apart (their token streams are asserted
        bit-identical, DESIGN.md §10). With ``sampling`` the decode chunks
        carry the device-resident sampling state (and, with ``dk > 0``,
        run speculative steps emitting 1..k+1 tokens each)."""
        t0 = time.perf_counter()
        sampled = sampling is not None
        dmargin = dk + 1 if dk else 0
        sstate = None
        if sampled:
            from repro.serve.sampling import pack_params, sampling_state
            sstate = sampling_state(self.max_batch, self.cfg.vocab_size)
        call = _ServeCall(
            t0=t0, cache=backend.init_cache(),
            cur=jnp.zeros((self.max_batch,), jnp.int32),
            done=jnp.ones((self.max_batch,), bool), sstate=sstate,
            outs=[[] for _ in prompts], free=list(range(self.max_batch)),
            stats=backend.stats)
        queue = deque(range(len(prompts)))

        # one reusable zero cache for every admission prefill (the jitted
        # prefill never donates it, so the template stays pristine)
        c1_template = registry.init_cache(self.cfg, 1, smax)

        def admit(slot: int, ridx: int):
            grant = backend.reserve(ridx, blens[ridx],
                                    budgets[ridx] + dmargin)
            if grant is None:
                return "defer"                       # wait for retirements
            call.stamp(call.assign_s, ridx)
            p, bl = prompts[ridx], blens[ridx]
            toks = np.zeros((1, bl), np.int32)
            toks[0, bl - len(p):] = p                # left-pad to bucket
            batch1 = {"tokens": jnp.asarray(toks),
                      "start": jnp.asarray([bl - len(p)], np.int32)}
            if sampled:
                fv, iv = pack_params(sampling[ridx])
                (nxt1, _), c1 = self._sample_prefill_fn("padded", use_tt)(
                    self.params, c1_template, batch1,
                    fv[None], iv[None])
            else:
                nxt1, c1 = self._prefill(self.params, c1_template, batch1)
            tok = int(jax.device_get(nxt1)[0])       # first generated token
            call.outs[ridx].append(tok)
            call.stamp(call.ttft_s, ridx)
            if tok == self.eos_id or budgets[ridx] <= 1:
                call.done_s[ridx] = call.ttft_s[ridx]
                backend.release(grant)
                return False                         # finished at prefill
            call.cache, call.cur, call.done = backend.admit(
                call.cache, c1, call.cur, call.done, slot, nxt1[0], grant)
            if sampled:
                call.sstate = self._sstate_admit(
                    call.sstate, jnp.int32(slot), fv, iv, nxt1[0])
            call.active[slot] = ridx
            call.left[ridx] = budgets[ridx] - 1
            return True

        it = 0
        while queue or call.active:
            with StepTraceAnnotation("serve.iter", step_num=it):
                it += 1
                # first-fit admission between decode chunks: a request whose
                # reservation doesn't fit yet is skipped (kept in arrival
                # order), not head-of-line blocking — short requests backfill
                # slots behind a deferred long one. The contiguous backend
                # always grants, which degenerates to plain FIFO fill.
                skipped: List[int] = []
                while queue and call.free:
                    ridx = queue.popleft()
                    if budgets[ridx] <= 0:
                        continue
                    slot = call.free.pop()
                    r = admit(slot, ridx)
                    if r == "defer":
                        call.free.append(slot)
                        skipped.append(ridx)
                        backend.stats["deferred_admissions"] += 1
                        continue
                    if not r:
                        call.free.append(slot)
                queue.extendleft(reversed(skipped))
                if not call.active:
                    if queue:  # deferred with nothing left to retire
                        backend.starved(queue[0], blens, budgets)
                    continue
                self._decode_chunk(call, chunk, backend, use_tt, dk,
                                   park=None)
        self.serve_stats = call.finish()
        return call.outs

    def _decode_chunk(self, call: _ServeCall, chunk: int, backend,
                      use_tt: bool, dk: int, park: Optional[int]) -> None:
        """One decode chunk over the whole batch, then token accounting
        and retirement: the one copy both serve loops run. Chunks have a
        fixed size (one compiled scan); rows that hit EOS or their budget
        mid-chunk have their surplus tokens discarded and retire at the
        chunk boundary. ``park``: the write cursor a retired contiguous
        row goes back to (see `_serve_loop_packed`); None leaves it.

        Counts, in ``call.stats``: ``decode_steps``, the steps dispatched
        (each steps every row); ``decode_row_steps``, the steps from which
        a live row consumed tokens (one each, but for speculative steps);
        ``decode_surplus_row_steps``, the steps a row ran after its EOS or
        budget inside the chunk, whose tokens are discarded."""
        stats = call.stats
        sampled = call.sstate is not None
        stats["peak_active"] = max(stats["peak_active"], len(call.active))
        with TraceAnnotation("serve.host.dispatch_decode"):
            if sampled:
                (call.cur, call.cache, call.done, call.sstate, e_d,
                 n_d) = self._sample_chunk_fn(chunk, use_tt, dk)(
                    self.params, call.cache, call.cur, call.done,
                    call.sstate)
            else:
                call.cur, call.cache, call.done, toks_d = self._chunk_fn(
                    chunk)(self.params, call.cache, call.cur, call.done)
        stats["decode_steps"] += chunk
        with TraceAnnotation("serve.sync.decode"):    # one fetch per chunk
            if sampled:
                host_e, host_n = np.asarray(e_d), np.asarray(n_d)
            else:
                host_e = np.asarray(toks_d)[:, :, None]
        with TraceAnnotation("serve.host.retire"):
            if not sampled:
                host_n = np.ones(host_e.shape[:2], np.int64)
            if dk:
                _bump_spec_stats(stats, host_n, call.active)
            retired = []
            for slot, ridx in call.active.items():
                call.left[ridx], fin, used = _consume_slot(
                    host_e, host_n, slot, call.outs[ridx], call.left[ridx],
                    self.eos_id)
                stats["decode_row_steps"] += used
                if fin:
                    stats["decode_surplus_row_steps"] += chunk - used
                    call.stamp(call.done_s, ridx)
                    retired.append(slot)
            for slot in retired:
                del call.active[slot]
                call.free.append(slot)
                call.done = call.done.at[slot].set(True)
                call.cache = backend.retire(call.cache, slot)
                if park is not None:
                    call.cache = dict(call.cache, length=call.cache[
                        "length"].at[slot].set(park))

    def _serve_loop_packed(self, prompts: List[List[int]],
                           budgets: List[int], blens: List[int], smax: int,
                           chunk: int, backend, prefill_chunk: int,
                           sampling: Optional[Sequence[Any]] = None,
                           use_tt: bool = False, dk: int = 0
                           ) -> List[List[int]]:
        """Padding-free continuous batching (DESIGN.md §12). Differences
        from `_serve_loop`:

        * Admission splits into slot ASSIGNMENT (reserve cache space, no
          compute) and PREFILL. Assigned-but-unfinished requests sit in
          ``pending``; their rows stay done=True, so decode never sees a
          half-prefilled prompt.
        * All first chunks pack into ONE cu_seqlens call per scheduler
          iteration — total_tokens of work, zero pad rows — and requests
          admit with start=0 (no left-pad: packed rows are solo-exact by
          construction, not by masking).
        * With ``prefill_chunk > 0`` at most that many prompt tokens
          prefill between consecutive decode chunks (continuations run
          FIFO, one chunk per row per iteration), which bounds the TTFT
          jitter a long admission inflicts on in-flight decode rows.

        Half-prefilled/free rows still decode-step (the chunk scan is
        whole-batch); their garbage K/V writes are neutralized by
        construction: contiguous rows park their write cursor at ``smax``
        (clamped writes land in slot smax-1, which chunk prefill never
        addresses and a live row always real-overwrites before attending);
        paged rows write through a block table still pointing at the
        reserved dummy page.

        Each iteration runs under a ``serve.iter`` profiler span, its host
        work under ``serve.host.*`` and its waits on the device under
        ``serve.sync.*`` (no-ops unless a profiler runs)."""
        t0 = time.perf_counter()
        sampled = sampling is not None
        dmargin = dk + 1 if dk else 0
        cache = backend.init_cache()
        paged = "k_pages" in cache
        if not paged:
            cache = dict(cache, length=jnp.full((self.max_batch,), smax,
                                                jnp.int32))
        sstate = None
        if sampled:
            from repro.serve.sampling import pack_params, sampling_state
            sstate = sampling_state(self.max_batch, self.cfg.vocab_size)
        call = _ServeCall(
            t0=t0, cache=cache, cur=jnp.zeros((self.max_batch,), jnp.int32),
            done=jnp.ones((self.max_batch,), bool), sstate=sstate,
            outs=[[] for _ in prompts], free=list(range(self.max_batch)),
            stats=backend.stats)
        queue = deque(range(len(prompts)))
        # slot -> [ridx, prefilled_offset, grant] (insertion order = FIFO)
        pending: Dict[int, list] = {}
        stats = call.stats
        stats.update(packed_prefill_tokens=0, prompt_tokens=0,
                     max_prefill_call_tokens=0)

        def bump(tokens_padded: int, tokens_real: int):
            stats["packed_prefill_tokens"] += tokens_padded
            stats["prompt_tokens"] += tokens_real
            stats["max_prefill_call_tokens"] = max(
                stats["max_prefill_call_tokens"], tokens_padded)

        def complete(slot: int, st: list, tok: int):
            ridx, grant = st[0], st[2]
            with TraceAnnotation("serve.host.install", ridx=ridx):
                call.outs[ridx].append(tok)
                call.stamp(call.ttft_s, ridx)
                del pending[slot]
                if tok == self.eos_id or budgets[ridx] <= 1:
                    call.done_s[ridx] = call.ttft_s[ridx]
                    backend.release(grant)
                    call.free.append(slot)
                    return
                call.cache, call.cur, call.done = backend.install(
                    call.cache, call.cur, call.done, slot, jnp.int32(tok),
                    len(prompts[ridx]), grant)
                if sampled:
                    fv, iv = pack_params(sampling[ridx])
                    call.sstate = self._sstate_admit(
                        call.sstate, jnp.int32(slot), fv, iv,
                        jnp.int32(tok))
                call.active[slot] = ridx
                call.left[ridx] = budgets[ridx] - 1

        def run_continue(slot: int, st: list) -> int:
            ridx, off = st[0], st[1]
            p = prompts[ridx]
            c = (min(len(p) - off, prefill_chunk) if prefill_chunk > 0
                 else len(p) - off)
            cp = _bucket_len(c, 8)
            with TraceAnnotation("serve.host.pack"):
                toks = np.zeros((1, cp), np.int32)
                toks[0, :c] = p[off:off + c]
                pos = off + np.arange(cp, dtype=np.int32)
                rows = np.full((cp,), backend.pad_row(), np.int32)
                cols = np.zeros((cp,), np.int32)
                rows[:c], cols[:c] = backend.token_addr(
                    slot, st[2], np.arange(off, off + c, dtype=np.int64))
                cargs = (jnp.asarray(toks), jnp.asarray(pos)[None],
                         jnp.asarray(rows), jnp.asarray(cols),
                         backend.kv_sel(slot, st[2]), jnp.int32(c - 1))
                if sampled:
                    fv, iv = pack_params(sampling[ridx])
            with TraceAnnotation("serve.host.dispatch_continue", ridx=ridx):
                if sampled:
                    (nxt, _), call.cache = self._sample_prefill_fn(
                        "chunk", use_tt)(self.params, call.cache, *cargs,
                                         fv[None], iv[None])
                else:
                    nxt, call.cache = self._prefill_continue(
                        self.params, call.cache, *cargs)
            st[1] = off + c
            bump(cp, c)
            if st[1] == len(p):
                with TraceAnnotation("serve.sync.first_token"):
                    tok = int(jax.device_get(nxt)[0])
                complete(slot, st, tok)
            return c

        def run_packed(items: List[tuple]) -> None:
            """One packed call over the first chunks of ``items``, each
            (slot, pending entry, chunk length)."""
            with TraceAnnotation("serve.host.pack"):
                packed, tp = _packed_call_args(
                    [prompts[st[0]][:c] for _, st, c in items],
                    [backend.token_addr(slot, st[2],
                                        np.arange(c, dtype=np.int64))
                     for slot, st, c in items],
                    backend.pad_row())
                if sampled:
                    gp = packed[-1].shape[0]
                    fvp = np.zeros((gp, 5), np.float32)
                    fvp[:, 1] = 1.0                  # spare rows: identity
                    fvp[:, 2] = 1.0
                    ivp = np.zeros((gp, 2), np.int32)
                    for i, (slot, st, c) in enumerate(items):
                        f, ivv = pack_params(sampling[st[0]])
                        fvp[i], ivp[i] = np.asarray(f), np.asarray(ivv)
                    extra = (jnp.asarray(fvp), jnp.asarray(ivp))
            with TraceAnnotation("serve.host.dispatch_prefill"):
                if sampled:
                    (nxt, _), call.cache = self._sample_prefill_fn(
                        "packed", use_tt)(self.params, call.cache, *packed,
                                          *extra)
                else:
                    nxt, call.cache = self._packed_prefill(
                        self.params, call.cache, *packed)
            bump(tp, sum(c for _, _, c in items))
            host_tok = None
            for i, (slot, st, c) in enumerate(items):
                st[1] = c
                if c == len(prompts[st[0]]):
                    if host_tok is None:         # one sync per packed call
                        with TraceAnnotation("serve.sync.first_token"):
                            host_tok = np.asarray(jax.device_get(nxt))
                    complete(slot, st, int(host_tok[i]))

        it = 0
        while queue or pending or call.active:
            with StepTraceAnnotation("serve.iter", step_num=it):
                it += 1
                # 1) slot assignment: reservation only, arrival order; a
                # deferred reservation (paged pool exhausted) is skipped, not
                # head-of-line blocking
                with TraceAnnotation("serve.host.assign"):
                    skipped: List[int] = []
                    while queue and call.free:
                        ridx = queue.popleft()
                        if budgets[ridx] <= 0:
                            continue
                        grant = backend.reserve(ridx, len(prompts[ridx]),
                                                budgets[ridx] + dmargin)
                        if grant is None:
                            skipped.append(ridx)
                            stats["deferred_admissions"] += 1
                            continue
                        pending[call.free.pop()] = [ridx, 0, grant]
                        call.stamp(call.assign_s, ridx)
                    queue.extendleft(reversed(skipped))
                if not pending and not call.active:
                    if queue:  # deferred with nothing left to retire
                        backend.starved(queue[0], blens, budgets)
                    continue

                # 2) prefill: ≤ prefill_chunk prompt tokens this iteration
                # (always ≥ one chunk of progress when anything is pending) —
                # continuations first, then the packed first-chunk call
                budget = prefill_chunk if prefill_chunk > 0 else float("inf")
                spent = 0
                for slot, st in list(pending.items()):
                    if st[1] == 0:
                        continue
                    if spent >= budget:
                        break
                    spent += run_continue(slot, st)
                items = []
                for slot, st in list(pending.items()):
                    if st[1] != 0:
                        continue
                    length = len(prompts[st[0]])
                    c = (min(length, prefill_chunk) if prefill_chunk > 0
                         else length)
                    if (spent > 0 or items) and spent + c > budget:
                        break
                    items.append((slot, st, c))
                    spent += c
                if items:
                    run_packed(items)

                # 3) decode chunk + retirement
                if call.active:
                    self._decode_chunk(call, chunk, backend, use_tt, dk,
                                       park=None if paged else smax)
        self.serve_stats = call.finish()
        return call.outs


# ---------------------------------------------------------------------------
# serve() KV backends: how cache space is reserved and admissions scatter
# ---------------------------------------------------------------------------

def _paged_unsupported_reason(cfg: ModelConfig, tp: int = 0) -> str:
    """Why the paged scheduler cannot serve this config (empty = it can).
    Its decode branch runs the flash kernel unconditionally, so it is
    only offered when the flash backend is what the contiguous engine
    would run too (same `_flash_backend` predicate — anything else, e.g.
    a pinned XLA oracle or the default xla GEMM route, would void the
    paged-vs-contiguous bit-identity contract) and when the GQA group
    passes the kernel's resident-query gate. Under the TP serving wrap
    (tp > 1) the predicate is evaluated as the shard bodies will see it —
    the live mesh alone no longer vetoes the kernel."""
    from repro.kernels.common import SKINNY_M_MAX, skinny_ok
    from repro.models.attention import _flash_backend
    if tp > 1:
        with shard_tp_ctx(tp):
            flash = _flash_backend(cfg)
    else:
        flash = _flash_backend(cfg)
    if not flash:
        return (f"flash attention backend inactive (attn_impl="
                f"{cfg.attn_impl!r}, gemm_impl={cfg.gemm_impl!r}; needs "
                "attn_impl='flash', or 'auto' with the Pallas route — "
                "single device, or per-shard under the TP serving wrap)")
    g = cfg.num_heads // max(1, cfg.num_kv_heads)
    if not skinny_ok(g, cfg.resolved_head_dim,
                     jnp.dtype(cfg.dtype).itemsize):
        return (f"GQA group size {g} exceeds the decode kernel's "
                f"resident-query gate (SKINNY_M_MAX={SKINNY_M_MAX})")
    return ""


class _ContiguousKvBackend:
    """Classic layout: every slot owns a reserved [smax] stripe of the
    shared cache. Reservations always succeed (slot availability is the
    only resource, and `_serve_loop` hands us a free slot)."""

    def __init__(self, eng: "ServeEngine", smax: int):
        self.eng = eng
        self.smax = smax
        self.stats: Dict[str, int] = {"peak_active": 0,
                                      "deferred_admissions": 0}

    def init_cache(self):
        cache = registry.init_cache(self.eng.cfg, self.eng.max_batch,
                                    self.smax)
        cache["start"] = jnp.zeros((self.eng.max_batch,), jnp.int32)
        return cache

    def reserve(self, ridx: int, blen: int, budget: int):
        return ()                                    # always grants

    def release(self, grant) -> None:
        pass

    def admit(self, cache, c1, cur, done, slot: int, tok, grant):
        return self.eng._admit(cache, c1, cur, done, jnp.int32(slot), tok)

    def retire(self, cache, slot: int):
        return cache                                 # slot stripe just idles

    def starved(self, ridx: int, blens, budgets) -> None:
        raise AssertionError("contiguous reservations cannot defer")

    # -- packed-prefill addressing (DESIGN.md §12) ------------------------

    def pad_row(self) -> int:
        """Out-of-range scatter row for packed padding tokens (dropped)."""
        return self.eng.max_batch

    def token_addr(self, slot: int, grant, pos: np.ndarray):
        """(rows, cols) scatter address for this request's token at each
        absolute position: its slot stripe, slot index = position."""
        return (np.full(pos.shape, slot, np.int32), pos.astype(np.int32))

    def kv_sel(self, slot: int, grant):
        return jnp.int32(slot)

    def install(self, cache, cur, done, slot: int, tok, length: int, grant):
        return self.eng._install(cache, cur, done, jnp.int32(slot), tok,
                                 jnp.int32(length))


class _PagedKvBackend:
    """Paged layout (DESIGN.md §10): requests reserve
    ``ceil((prompt + budget) / page)`` pages from a shared pool instead of
    an smax stripe, so a fixed HBM budget packs requests by what they
    actually use. Deferred reservations wait for retirements to free
    pages; retirement also points the slot's block table at the reserved
    dummy page so the retired-but-still-stepping row's overshoot writes
    land harmlessly instead of corrupting recycled pages."""

    def __init__(self, eng: "ServeEngine", smax: int):
        from repro.kernels.attn import paged_decode_ok
        from repro.serve.kv_cache import PageAllocator
        cfg = eng.cfg
        self.eng = eng
        self.smax = smax
        self.page = cfg.kv_page_size
        assert self.page > 0, "paged serving needs cfg.kv_page_size > 0"
        if self.page < 8:
            # the contiguous flash-decode gate (attention.py) rejects
            # sub-sublane pages; accepting them here would put the two
            # schedulers on different numeric paths
            raise ValueError(
                f"kv_page_size={self.page} below the minimum page of 8 "
                "slots (sublane quantum)")
        if not paged_decode_ok(self.page, cfg.num_kv_heads,
                               cfg.resolved_head_dim,
                               jnp.dtype(cfg.dtype).itemsize):
            raise ValueError(
                f"kv_page_size={self.page} makes a KV page tile that "
                "cannot fit the decode kernel's VMEM budget — lower it")
        self.n_log = smax // self.page
        self.pool_pages = (eng.kv_pool_pages
                           or (eng.max_batch * self.n_log + 1))
        self.alloc = PageAllocator(self.pool_pages)
        self.slot_pages: Dict[int, List[int]] = {}   # slot -> phys pages
        self.stats: Dict[str, int] = {
            "peak_active": 0, "deferred_admissions": 0,
            "pool_pages": self.pool_pages, "page": self.page,
            "n_log": self.n_log}

    def init_cache(self):
        from repro.serve.kv_cache import init_paged_cache
        return init_paged_cache(self.eng.cfg, self.eng.max_batch,
                                self.pool_pages, self.page, self.n_log)

    def reserve(self, ridx: int, blen: int, budget: int):
        from repro.serve.kv_cache import pages_needed
        need = pages_needed(blen, budget, self.page)
        if need > self.pool_pages - 1:
            raise RuntimeError(
                f"request {ridx} needs {need} pages; pool has "
                f"{self.pool_pages - 1} usable — raise kv_pool_pages")
        return self.alloc.alloc(need)                # None = defer

    def release(self, grant: List[int]) -> None:
        self.alloc.free(grant)

    def admit(self, cache, c1, cur, done, slot: int, tok,
              grant: List[int]):
        row = np.zeros((self.n_log,), np.int32)      # tail -> dummy page
        row[:len(grant)] = grant
        self.slot_pages[slot] = grant
        return self.eng._admit_paged(cache, c1, cur, done,
                                     jnp.asarray(row), jnp.int32(slot), tok)

    def retire(self, cache, slot: int):
        self.alloc.free(self.slot_pages.pop(slot))
        # stale decode writes of this still-stepping slot must not touch
        # the recycled pages: point its table at the dummy
        cache["block_table"] = cache["block_table"].at[slot].set(0)
        return cache

    def starved(self, ridx: int, blens, budgets) -> None:
        from repro.serve.kv_cache import pages_needed
        raise RuntimeError(
            f"request {ridx} cannot be admitted: needs "
            f"{pages_needed(blens[ridx], budgets[ridx], self.page)} "
            f"pages, pool has {self.alloc.free_pages} free")

    # -- packed-prefill addressing (DESIGN.md §12) ------------------------

    def pad_row(self) -> int:
        """Out-of-range scatter row for packed padding tokens: one past
        the pool (the dummy page 0 is a real pool page — pads must not
        collide with it)."""
        return self.pool_pages

    def token_addr(self, slot: int, grant, pos: np.ndarray):
        """Physical (page, offset) per absolute position through the
        granted page list — packed prefill writes the pool directly; the
        block table only learns about these pages at install time."""
        g = np.asarray(grant, np.int64)
        return (g[pos // self.page].astype(np.int32),
                (pos % self.page).astype(np.int32))

    def kv_sel(self, slot: int, grant):
        row = np.zeros((self.n_log,), np.int32)      # tail -> dummy page
        row[:len(grant)] = grant
        return jnp.asarray(row)

    def install(self, cache, cur, done, slot: int, tok, length: int, grant):
        row = np.zeros((self.n_log,), np.int32)      # tail -> dummy page
        row[:len(grant)] = grant
        self.slot_pages[slot] = grant
        return self.eng._install_paged(cache, cur, done, jnp.asarray(row),
                                       jnp.int32(slot), tok,
                                       jnp.int32(length))
