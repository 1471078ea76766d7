"""Reference sampling math shared by the fused head-sample kernel and the
XLA fallback route (DESIGN.md §15).

Everything here is plain ``jnp`` so the exact same ops run inside the
Pallas kernel (interpret mode) and in the XLA reference sampler — that is
what makes the fused route *bit-exact* with the reference at a fixed key:

  * **Counter-based RNG.** A murmur-finalizer hash of
    ``(seed, step, global vocab index, salt)`` in uint32. Noise depends
    only on those four values — never on batch slot, chunk size, tile
    order, or TP shard layout — so sampled streams are reproducible
    across chunk sizes and across TP vs single-device runs by
    construction. Salt streams keep the token-sampling, acceptance, and
    resample draws independent.
  * **Penalty contract** (mirrors TensorRT-LLM's
    ``samplingPenaltyKernels``): repetition divides positive /
    multiplies negative logits of seen tokens, presence subtracts a
    flat penalty from seen tokens, frequency subtracts
    ``count * penalty``. "Seen" means present in the *output-token
    history* (``counts > 0``); the prompt is not penalised. All three
    are exact identities at their default values (1.0 / 0.0 / 0.0), so
    default sampling at temperature 0 is bit-identical to greedy.
  * **Gumbel-max sampling.** ``argmax(logits / T + gumbel)`` is a
    categorical draw from ``softmax(logits / T)``; at temperature 0 the
    noise is skipped entirely and the score *is* the penalised logit, so
    the argmax degenerates to greedy exactly (no ``0 * inf`` traps).

Uniforms are built as ``((h >> 9) + 0.5) * 2^-23`` — every intermediate
is exactly representable in f32, and the result lies strictly inside
``(0, 1)`` (min ``2^-24``, max ``1 - 2^-24``), so ``log(u)`` is finite
and acceptance ratios of exactly 0 / 1 behave deterministically.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SALT_TOKEN", "SALT_ACCEPT", "SALT_RESAMPLE", "NEG_INF",
    "hash_u32", "uniform_noise", "gumbel_noise",
    "apply_penalties", "inv_temperature", "mask_top_k", "mask_top_p",
    "sample_scores", "sample_argmax", "sample_logits", "probs_from_logits",
]

# Same sentinel the attention masks use — finite, so arithmetic on masked
# lanes stays NaN-free.
NEG_INF = -1e30

# Independent noise streams (static Python ints, baked into the trace).
SALT_TOKEN = 0     # per-step token sampling (gumbel)
SALT_ACCEPT = 1    # speculative acceptance uniforms
SALT_RESAMPLE = 2  # residual-distribution resample (gumbel)

# np scalars, not jnp arrays: they bind as jaxpr literals, so the Pallas
# kernel can use these helpers without capturing traced constants.
_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)


def _mix(h: jax.Array) -> jax.Array:
    """Murmur3 finalizer — full avalanche on a uint32."""
    h = h ^ (h >> np.uint32(16))
    h = h * _C1
    h = h ^ (h >> np.uint32(13))
    h = h * _C2
    h = h ^ (h >> np.uint32(16))
    return h


def hash_u32(seed: jax.Array, step: jax.Array, idx: jax.Array,
             salt: int) -> jax.Array:
    """Counter-based hash of (seed, step, idx, salt) → uint32.

    Inputs may be any mutually-broadcastable shapes; each is folded in
    through a full-avalanche mix so per-row seeds, per-row step counters
    and global vocab indices all decorrelate.
    """
    # the salt product folds on the host (masked python int — numpy scalar
    # wraparound would warn) and binds as one u32 literal
    h = _mix(seed.astype(jnp.uint32)
             + np.uint32((0x9E3779B9 * (salt + 1)) & 0xFFFFFFFF))
    h = _mix(h ^ step.astype(jnp.uint32))
    h = _mix(h ^ idx.astype(jnp.uint32))
    return h


def uniform_noise(seed, step, idx, salt: int) -> jax.Array:
    """Uniform f32 strictly inside (0, 1); every op exact in f32. The
    23-bit draw goes through int32 (exact: ``h >> 9 < 2**23``) because the
    TPU has no uint32 → f32 conversion."""
    h = hash_u32(seed, step, idx, salt)
    top = (h >> np.uint32(9)).astype(jnp.int32)
    return (top.astype(jnp.float32) + np.float32(0.5)) * np.float32(2.0 ** -23)


def gumbel_noise(seed, step, idx, salt: int) -> jax.Array:
    u = uniform_noise(seed, step, idx, salt)
    return -jnp.log(-jnp.log(u))


def apply_penalties(logits: jax.Array, counts: jax.Array, rep: jax.Array,
                    pres: jax.Array, freq: jax.Array) -> jax.Array:
    """TensorRT-LLM penalty contract, in place on (a tile of) logits.

    ``logits`` f32 and ``counts`` i32 share a shape ``[..., n]``;
    ``rep``/``pres``/``freq`` are per-row f32 broadcastable against them
    (``[B, 1]`` against ``[B, n]``). Defaults (1, 0, 0) are exact
    identities: ``x / 1 == x * 1 == x`` and ``x - 0 == x`` bit-exactly.
    """
    seen = counts > 0
    cf = counts.astype(logits.dtype)
    scaled = jnp.where(logits > 0, logits / rep, logits * rep)
    out = jnp.where(seen, scaled, logits)
    out = out - cf * freq
    out = out - jnp.where(seen, pres, jnp.zeros_like(pres))
    return out


def inv_temperature(temp: jax.Array) -> jax.Array:
    """1/T for T > 0, else 1 — no inf/NaN in either branch."""
    safe = jnp.where(temp > 0, temp, jnp.ones_like(temp))
    return jnp.where(temp > 0, 1.0 / safe, jnp.ones_like(temp))


def mask_top_k(logits: jax.Array, top_k: jax.Array) -> jax.Array:
    """Keep each row's top-k logits, mask the rest to NEG_INF.

    ``top_k`` [B] int32; values <= 0 disable the filter for that row.
    Needs the full row (global order statistic) — XLA route only.
    """
    v = logits.shape[-1]
    k = jnp.where(top_k > 0, top_k, v).astype(jnp.int32)
    desc = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
    kth = jnp.take_along_axis(
        desc, jnp.clip(k - 1, 0, v - 1)[:, None], axis=-1)
    masked = jnp.where(logits >= kth, logits, jnp.float32(NEG_INF))
    return jnp.where((top_k > 0)[:, None], masked, logits)


def mask_top_p(logits: jax.Array, top_p: jax.Array) -> jax.Array:
    """Nucleus filter: keep the smallest prefix of the descending-prob
    row whose cumulative mass reaches top_p. ``top_p`` [B] f32; values
    >= 1 disable the filter for that row. XLA route only."""
    desc = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # A token stays if the mass *before* it is still under top_p.
    keep = (cum - probs) < top_p[:, None]
    kth = jnp.min(jnp.where(keep, desc, jnp.float32(jnp.inf)),
                  axis=-1, keepdims=True)
    masked = jnp.where(logits >= kth, logits, jnp.float32(NEG_INF))
    return jnp.where((top_p < 1.0)[:, None], masked, logits)


def sample_scores(logits, counts, temp, rep, pres, freq, seed, step,
                  idx, *, salt: int = SALT_TOKEN) -> jax.Array:
    """Penalty → temperature → gumbel score for (a tile of) logits.

    Per-row params arrive as ``[B, 1]``; ``idx`` holds the *global*
    vocab index of each column (``[B, n]`` or ``[1, n]``). This is the
    exact epilogue the fused kernel runs per N tile — the argmax of the
    full-row scores is the sampled token.
    """
    pen = apply_penalties(logits, counts, rep, pres, freq)
    inv_t = inv_temperature(temp)
    g = gumbel_noise(seed, step, idx, salt)
    return jnp.where(temp > 0, pen * inv_t + g, pen)


def sample_argmax(logits, counts, temp, rep, pres, freq, seed, step,
                  *, base=0, top_k=None, top_p=None,
                  use_tt: bool = False):
    """Full-row scores → (best score [B] f32, argmax [B] i32 LOCAL index).

    The XLA twin of the fused kernel's output pair: ``base`` offsets the
    noise counter to global vocab ids (vocab-parallel TP shards pass
    ``shard * v_local``), while the returned index stays local so the
    caller's combine adds the shard offset exactly once. ``use_tt`` is a
    *static* flag: when False no top-k/top-p code is traced at all, so
    default params at temperature 0 reduce to a plain argmax. When True
    the logits must be the full (unsharded) row — the nucleus masks are
    global order statistics.
    """
    b, v = logits.shape
    col = jnp.asarray(base, jnp.int32).reshape(-1, 1) \
        + jnp.arange(v, dtype=jnp.int32)[None, :]
    t = temp.reshape(b, 1)
    pen = apply_penalties(logits, counts, rep.reshape(b, 1),
                          pres.reshape(b, 1), freq.reshape(b, 1))
    if use_tt:
        pen = mask_top_k(pen, top_k)
        pen = mask_top_p(pen, top_p)
    inv_t = inv_temperature(t)
    g = gumbel_noise(seed.reshape(b, 1), step.reshape(b, 1), col,
                     SALT_TOKEN)
    score = jnp.where(t > 0, pen * inv_t + g, pen)
    return (jnp.max(score, axis=-1),
            jnp.argmax(score, axis=-1).astype(jnp.int32))


def sample_logits(logits, counts, temp, top_k, top_p, rep, pres, freq,
                  seed, step, *, use_tt: bool = False) -> jax.Array:
    """XLA reference sampler: [B, V] logits → [B] int32 token ids."""
    _, tok = sample_argmax(logits, counts, temp, rep, pres, freq, seed,
                           step, top_k=top_k, top_p=top_p, use_tt=use_tt)
    return tok


def probs_from_logits(logits, counts, temp, rep, pres, freq) -> jax.Array:
    """Post-penalty sampling distribution ``[..., V]`` for the
    speculative accept/reject rule.

    Rows with temperature 0 get a one-hot at the greedy argmax (first
    max, matching ``jnp.argmax``) instead of a softmax over ``x / 0``.
    ``temp``/``rep``/``pres``/``freq`` broadcast against the leading
    dims of ``logits`` (e.g. ``[B, 1, 1]`` against ``[B, T, V]``).
    """
    v = logits.shape[-1]
    pen = apply_penalties(logits, counts, rep, pres, freq)
    inv_t = inv_temperature(temp)
    soft = jax.nn.softmax(pen * inv_t, axis=-1)
    hard = jax.nn.one_hot(jnp.argmax(pen, axis=-1), v, dtype=soft.dtype)
    return jnp.where(temp > 0, soft, hard)
