"""Vocab-parallel collectives + dense oracles.

The two ops whose naive forms materialize [tokens, V] tensors are the
embedding gather and the LM-head cross-entropy. Both get shard_map
implementations that keep the vocab axis sharded over "model": each shard
works on its vocab slice and one psum combines the scalars — unsharded
logits never exist (DESIGN.md §6 discusses why this matters at V ≥ 100k).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.dist.mesh_ctx import current_mesh

__all__ = ["dense_ce", "dense_ce_chunked", "vocab_parallel_ce",
           "vocab_parallel_embed", "cross_entropy", "axis_size",
           "overlapped_psum", "shard_embed_lookup", "shard_greedy",
           "shard_sample", "greedy_vocab_parallel", "greedy_scatter"]


def axis_size(name: str = "model") -> int:
    """Size of a named collective axis, from inside a shard_map/pmap body.

    ``jax.lax.psum(1, name)`` is the canonical trick — jax folds a psum of
    the unit constant to the axis size at trace time. Outside any axis
    binding jax raises a bare ``NameError``/``KeyError`` naming the axis;
    wrap it in an actionable error instead. (For the *mesh* axis size
    outside a shard body, use `repro.dist.mesh_ctx.axis_size`, which
    returns 1 when no mesh is live.)"""
    try:
        return int(jax.lax.psum(1, name))
    except (NameError, KeyError, ValueError) as e:
        raise RuntimeError(
            f"collectives.axis_size({name!r}) called outside a mesh/"
            f"shard_map context: no collective axis named {name!r} is "
            "bound. Call it from inside a shard_map body (e.g. under "
            "serve's shard_tp_ctx), or use repro.dist.mesh_ctx.axis_size "
            "for the context-mesh axis size.") from e


def overlapped_psum(y: jax.Array, axis: str = "model",
                    chunks: int = 2) -> jax.Array:
    """Boundary all-reduce split along the last dim into ``chunks``
    independent psums. Each element is still summed exactly once, so the
    result is bit-identical to one psum — but the chunks are independent
    collective ops, which lets XLA's async collective scheduler start the
    first chunk's wire transfer while the producing GEMM's epilogue is
    still storing the later chunks (the overlap timeline in DESIGN.md
    §14). Falls back to a single psum when the dim doesn't split."""
    if chunks <= 1 or y.shape[-1] % chunks != 0:
        return jax.lax.psum(y, axis)
    parts = jnp.split(y, chunks, axis=-1)
    return jnp.concatenate([jax.lax.psum(p, axis) for p in parts], axis=-1)


def _masked_mean(nll: jax.Array, mask: Optional[jax.Array]) -> jax.Array:
    if mask is None:
        return nll.mean()
    m = mask.astype(jnp.float32)
    return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)


def dense_ce(h: jax.Array, w: jax.Array, labels: jax.Array,
             mask: Optional[jax.Array] = None) -> jax.Array:
    """Token-mean CE with full [.., V] logits. h [B,S,d] · w [d,V]."""
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return _masked_mean(lse - ll, mask)


def dense_ce_chunked(h: jax.Array, w: jax.Array, labels: jax.Array,
                     mask: Optional[jax.Array] = None,
                     rows: int = 8192) -> jax.Array:
    """CE with token-chunked logits (§Perf: live logits capped at
    [rows, V]); each chunk is rematerialized in the backward pass, so
    gradients are bit-identical to `dense_ce` up to reduction order."""
    b, s, d = h.shape
    t = b * s
    hf = h.reshape(t, d)
    lf = labels.reshape(t)
    mf = (jnp.ones((t,), jnp.float32) if mask is None
          else mask.reshape(t).astype(jnp.float32))
    # pad the token axis up to a rows multiple (mask 0 ⇒ zero contribution)
    # rather than searching for a divisor — a prime t would otherwise
    # collapse to one chunk and materialize the full [t, V] logits, the
    # exact blow-up this path exists to cap
    rows_eff = min(rows, t)
    t_pad = -(-t // rows_eff) * rows_eff
    if t_pad != t:
        hf = jnp.pad(hf, ((0, t_pad - t), (0, 0)))
        lf = jnp.pad(lf, (0, t_pad - t))
        mf = jnp.pad(mf, (0, t_pad - t))
    n_chunks = t_pad // rows_eff

    @jax.checkpoint
    def one(carry, xs):
        hc, lc, mc = xs
        logits = hc.astype(jnp.float32) @ w.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        nll_sum, m_sum = carry
        return (nll_sum + ((lse - ll) * mc).sum(), m_sum + mc.sum()), None

    (nll_sum, m_sum), _ = jax.lax.scan(
        one, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hf.reshape(n_chunks, rows_eff, d),
         lf.reshape(n_chunks, rows_eff),
         mf.reshape(n_chunks, rows_eff)))
    return nll_sum / jnp.maximum(m_sum, 1.0)


def vocab_parallel_ce(h: jax.Array, w: jax.Array, labels: jax.Array,
                      mesh, mask: Optional[jax.Array] = None) -> jax.Array:
    """CE with the head weight column-sharded over "model": each shard
    computes its vocab slice's partial logsumexp and the label logit when
    the label lands in its slice; two scalar psums combine them."""
    tp = mesh.shape["model"]
    v = w.shape[-1]
    v_loc = v // tp

    def shard_fn(hl, wl, lab, m):
        idx = jax.lax.axis_index("model")
        logits = hl.astype(jnp.float32) @ wl.astype(jnp.float32)
        # global logsumexp = logsumexp over per-shard logsumexps. The
        # gathered piece is [tp, ...] scalars-per-token — tiny — and
        # all_gather (unlike pmax) differentiates cleanly on every jax.
        lse_loc = jax.nn.logsumexp(logits, axis=-1)
        lse = jax.nn.logsumexp(
            jax.lax.all_gather(lse_loc, "model"), axis=0)
        # label logit: owned by exactly one shard
        lab_loc = lab - idx * v_loc
        in_range = (lab_loc >= 0) & (lab_loc < v_loc)
        safe = jnp.clip(lab_loc, 0, v_loc - 1)
        ll_loc = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        ll = jax.lax.psum(jnp.where(in_range, ll_loc, 0.0), "model")
        return _masked_mean(lse - ll, m)

    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    return shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(None, "model"), P(), P()),
        out_specs=P(),
        check_vma=False)(h, w, labels, mask)


def shard_embed_lookup(table_local: jax.Array, tokens: jax.Array, dtype,
                       axis: str = "model") -> jax.Array:
    """Per-shard body of the row-sharded embedding gather: the local table
    holds one contiguous vocab slice; serve the in-slice tokens and psum
    the rest to zero-contributions. Callable from any shard_map body over
    ``axis`` (the TP serving wrapper enters here via `embed_apply` when
    `shard_tp()` is live)."""
    idx = jax.lax.axis_index(axis)
    v_loc = table_local.shape[0]
    loc = tokens - idx * v_loc
    in_range = (loc >= 0) & (loc < v_loc)
    safe = jnp.clip(loc, 0, v_loc - 1)
    emb = table_local[safe].astype(jnp.float32)
    emb = jnp.where(in_range[..., None], emb, 0.0)
    return jax.lax.psum(emb, axis).astype(dtype)


def vocab_parallel_embed(table: jax.Array, tokens: jax.Array, dtype,
                         mesh) -> jax.Array:
    """Row-sharded embedding gather: each shard serves the tokens that fall
    in its vocab slice, one psum assembles the [B, S, d] output — the
    [V, d] table is never all-gathered."""
    out = shard_map(
        lambda tl, toks: shard_embed_lookup(tl, toks, jnp.float32),
        mesh=mesh,
        in_specs=(P("model", None), P()),
        out_specs=P(),
        check_vma=False)(table, tokens)
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# vocab-parallel greedy head (serving): the decode-step argmax without an
# unsharded [B, vocab] logits tensor ever existing (DESIGN.md §14)
# ---------------------------------------------------------------------------

def _greedy_combine(logits_loc: jax.Array, axis: str = "model") -> jax.Array:
    """Global greedy argmax from per-shard [B, v/tp] logit slices. Each
    shard reduces its slice to one (max, argmax) pair per row; the only
    cross-shard traffic is the [tp, B] all_gather of those scalars.
    Tie-breaking matches `jnp.argmax` on the full vector: shards are
    ordered by vocab offset and `argmax` picks the first maximum both
    within a slice and across the gathered axis."""
    v_loc = logits_loc.shape[-1]
    idx = jax.lax.axis_index(axis)
    loc_max = logits_loc.max(axis=-1)                       # [B]
    loc_arg = logits_loc.argmax(axis=-1) + idx * v_loc      # global ids
    all_max = jax.lax.all_gather(loc_max, axis)             # [tp, B]
    all_arg = jax.lax.all_gather(loc_arg, axis)             # [tp, B]
    winner = jnp.argmax(all_max, axis=0)                    # [B]
    return jnp.take_along_axis(
        all_arg, winner[None], axis=0)[0].astype(jnp.int32)


def shard_greedy(h: jax.Array, w_head_local: jax.Array, *,
                 impl: str = "xla", cfg=None,
                 axis: str = "model") -> jax.Array:
    """Greedy head GEMV from inside a shard_map body: ``w_head_local``
    is the column slice [d, v/tp], so the GEMV itself is local (the
    skinny Pallas route applies at the local width) and only the scalar
    (max, argmax) combine crosses shards."""
    from repro.kernels import dispatch
    logits = dispatch.matmul(h, w_head_local.astype(jnp.float32), cfg=cfg,
                             pallas=(impl == "pallas"), gemv=True)
    return _greedy_combine(logits, axis)


def shard_sample(h: jax.Array, w_head_local: jax.Array, counts: jax.Array,
                 temp, rep, pres, freq, seed, step, *,
                 top_k=None, top_p=None, use_tt: bool = False,
                 impl: str = "xla", cfg=None,
                 axis: str = "model") -> jax.Array:
    """Vocab-parallel sampling head from inside a shard_map body — the
    sampling twin of `shard_greedy` (DESIGN.md §15).

    Each shard runs the head GEMV + sampling epilogue on its column
    slice ``[d, v/tp]`` with noise keyed to GLOBAL vocab ids (the shard
    offset feeds the counter hash), reduces to one (best score, global
    argmax) pair per row, and the same [tp, B] scalar all_gather combine
    the greedy head uses picks the winner — bit-identical to a
    single-device run over the full row, because per-shard scores equal
    the corresponding slice of the full-row scores and the combine keeps
    `jnp.argmax`'s first-max order across vocab-ordered shards.

    ``counts`` arrives replicated ``[B, V]`` (it is per-request state,
    not weight); each shard slices its window. ``use_tt`` (STATIC) is
    the top-k/top-p escape hatch: the masks are global order statistics,
    so the shards all-gather the [B, V] logits once and run the full XLA
    reference sampler identically — correctness over wire-efficiency for
    the rows that ask for it.
    """
    from repro.kernels import dispatch
    idx = jax.lax.axis_index(axis)
    v_loc = w_head_local.shape[-1]
    base = idx * v_loc
    if use_tt:
        from repro.kernels.sample.ref import sample_logits
        logits_loc = dispatch.matmul(h, w_head_local.astype(jnp.float32),
                                     cfg=cfg, pallas=(impl == "pallas"),
                                     gemv=True)
        logits = jax.lax.all_gather(logits_loc, axis, axis=-1, tiled=True)
        return sample_logits(logits, counts, temp, top_k, top_p, rep,
                             pres, freq, seed, step, use_tt=True)
    counts_loc = jax.lax.dynamic_slice_in_dim(counts, base, v_loc, axis=1)
    score, tok_loc = dispatch.head_sample(
        h, w_head_local, counts_loc, temp, rep, pres, freq, seed, step,
        base=base, cfg=cfg, pallas=(impl == "pallas"), return_score=True)
    all_max = jax.lax.all_gather(score, axis)               # [tp, B]
    all_arg = jax.lax.all_gather(tok_loc + base, axis)      # global ids
    winner = jnp.argmax(all_max, axis=0)
    return jnp.take_along_axis(
        all_arg, winner[None], axis=0)[0].astype(jnp.int32)


def greedy_vocab_parallel(hidden: jax.Array, w_head: jax.Array, mesh,
                          *, impl: str = "xla", cfg=None) -> jax.Array:
    """Vocab-parallel greedy head for a *global* graph under a mesh:
    column-shards the head weight over "model", computes each [B, v/tp]
    logit slice per shard and combines (max, argmax) scalars. The GSPMD
    alternative (sharded matmul + global argmax) all-gathers the full
    [B, vocab] logits every step; here the wire carries [tp, B] scalars.
    ``hidden`` is the last-position activations [B, d] (f32)."""
    def shard_fn(hl, wl):
        return shard_greedy(hl, wl, impl=impl, cfg=cfg)

    return shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(None, "model")),
        out_specs=P(),
        check_vma=False)(hidden, w_head)


def greedy_scatter(hidden: jax.Array, w_head: jax.Array, mesh,
                   ) -> jax.Array:
    """`psum_scatter`-based vocab-parallel greedy head for a K(d)-sharded
    head weight (ZeRO'd lm_head / row-sharded tied table): each shard
    holds partial [B, vocab] logits from its d-slice; `psum_scatter`
    reduces them straight into per-shard [B, vocab/tp] slices — each hop
    moves [B, vocab/tp], never all-gathering the full [B, vocab] — and
    the same scalar (max, argmax) combine finishes the argmax."""
    tp = mesh.shape["model"]
    v = w_head.shape[-1]
    assert v % tp == 0, (v, tp)

    def shard_fn(hl, wl):
        partial = hl.astype(jnp.float32) @ wl.astype(jnp.float32)
        mine = jax.lax.psum_scatter(partial, "model",
                                    scatter_dimension=partial.ndim - 1,
                                    tiled=True)           # [B, v/tp]
        return _greedy_combine(mine, "model")

    return shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(None, "model"), P("model", None)),
        out_specs=P(),
        check_vma=False)(hidden, w_head)


def cross_entropy(hidden: jax.Array, w_head: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None,
                  vocab_parallel: bool = True) -> jax.Array:
    """LM-head CE dispatcher: vocab-parallel when a mesh with a non-trivial
    model axis is live and the vocab divides; token-chunked dense when the
    full logits tensor would be large; plain dense otherwise."""
    mesh = current_mesh()
    v = w_head.shape[-1]
    if (vocab_parallel and mesh is not None and "model" in mesh.axis_names
            and mesh.shape["model"] > 1 and v % mesh.shape["model"] == 0):
        return vocab_parallel_ce(hidden, w_head, labels, mesh, mask)
    tokens = 1
    for s in labels.shape:
        tokens *= s
    if tokens * v > (1 << 28):          # cap live logits at ~1 GB f32
        return dense_ce_chunked(hidden, w_head, labels, mask)
    return dense_ce(hidden, w_head, labels, mask)
