"""Plain float32 reference of the decoder a configuration file states.

Pre-norm decoder: embedding times ``embed_multiplier``; per layer
``x += o(attn(norm1(x)))`` with RoPE (split halves), grouped KV heads,
causal mask and an optional sliding window, then ``x += mlp(norm2(x))``
(gated ``act(x Wg) * (x Wu)`` or plain ``act(x Wu)``, then ``Wd``); final
norm; head ``x Wh`` (the embedding's transpose when tied). Everything is
float32 at ``Precision.HIGHEST``, one sequence at a time, one layer at a
time, with no cache and no batching.

It imports nothing of the program under test: its weights come from
``chipbench.weights`` and its sequences from what the program served.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512        # query rows per attention block (bounds the scores)
BUCKET = 512         # shortest padded length (a multiple of Q_BLOCK)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _norm(kind: str, p: dict, x, eps: float):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    if kind == "layernorm":
        y = y * p["scale"] + p["bias"]
    return y


def _rope(x, theta: float):
    """x [T, H, D]: rotate (first half, second half) pairs by position."""
    t, _, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, a * s + b * c], -1)


def _act(name: str, x):
    if name == "silu":
        return x * jax.nn.sigmoid(x)
    if name == "gelu":       # tanh form, as the configurations state
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    raise ValueError(name)


def _lin(x, w, b=None):
    y = jnp.dot(x, w, precision=HI)
    return y if b is None else y + b


def _attention(q, k, v, window: int):
    """q [T, Hq, D], k/v [T, Hkv, D]; causal (and windowed) softmax, one
    block of ``Q_BLOCK`` query rows at a time (T is a multiple of it)."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    qb = q.reshape(t // Q_BLOCK, Q_BLOCK, hkv, hq // hkv, d) / math.sqrt(d)
    kpos = jnp.arange(t)

    def block(args):
        qi, q0 = args
        qpos = q0 + jnp.arange(Q_BLOCK)
        s = jnp.einsum("thgd,shd->hgts", qi, k, precision=HI)
        ok = kpos[None, :] <= qpos[:, None]
        if window > 0:
            ok &= kpos[None, :] > qpos[:, None] - window
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgts,shd->thgd", p, v, precision=HI)

    out = jax.lax.map(block, (qb, jnp.arange(0, t, Q_BLOCK)))
    return out.reshape(t, hq * d)


@functools.partial(jax.jit, static_argnums=(2,))
def _layer(t, x, cfg_items):
    cfg = dict(cfg_items)
    hq, hkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps = cfg["norm_eps"]
    t = _f32(t)
    h = _norm(cfg["norm"], t["ln1"], x, eps)
    q = _lin(h, t["wq"], t.get("bq")).reshape(-1, hq, hd)
    k = _lin(h, t["wk"], t.get("bk")).reshape(-1, hkv, hd)
    v = _lin(h, t["wv"], t.get("bv")).reshape(-1, hkv, hd)
    if cfg["rope"]:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    att = _attention(q, k, v, cfg["sliding_window"])
    x = x + _lin(att, t["wo"])
    h = _norm(cfg["norm"], t["ln2"], x, eps)
    up = _lin(h, t["w_up"])
    if cfg["mlp_gated"]:
        up = _act(cfg["act"], _lin(h, t["w_gate"])) * up
    else:
        up = _act(cfg["act"], up)
    return x + _lin(up, t["w_down"])


@functools.partial(jax.jit, static_argnums=(3,))
def _embed(top, tokens, mult, cfg_items):
    return top["embed"].astype(jnp.float32)[tokens] * mult


def _logits(top, x, rows, cfg):
    h = _norm(cfg["norm"], top["final_norm"], x[rows], cfg["norm_eps"])
    head = top["embed"].T if cfg["tie_embeddings"] else top["head"]
    return _lin(h, head)


@functools.partial(jax.jit, static_argnums=(4,))
def _gaps(top, x, rows, served, cfg_items):
    """Gap of each served token below the best logit at its position."""
    logits = _logits(_f32(top), x, rows, dict(cfg_items))
    mine = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=1) - mine


def _static(cfg: dict):
    keys = ("num_heads", "num_kv_heads", "head_dim", "norm", "norm_eps",
            "rope", "rope_theta", "sliding_window", "mlp_gated", "act",
            "tie_embeddings")
    return tuple((k, cfg[k]) for k in keys)


def _bucket(n: int) -> int:
    """Padded length: ``BUCKET``, or a multiple of twice it, so that few
    programs serve every length and later runs find them compiled."""
    if n <= BUCKET:
        return BUCKET
    return -(-n // (2 * BUCKET)) * 2 * BUCKET


def embed_multiplier(cfg: dict) -> float:
    m = cfg["embed_multiplier"]
    if m == "sqrt_d_model":
        return math.sqrt(cfg["d_model"])
    return float(m)


def served_gaps(cfg: dict, layer_tensors, top_tensors, requests):
    """For each ``(prompt, served)`` pair, the gap by which every served
    token's logit lies below the reference's best logit at its position.

    ``layer_tensors(l)`` and ``top_tensors()`` give the configuration's
    weights (in the type they are served in); each sequence is the prompt
    followed by all served tokens but the last, and the scored positions
    are the prompt's last and every one after it.
    """
    st = _static(cfg)
    mult = embed_multiplier(cfg)
    top = top_tensors()
    xs = []
    for prompt, served in requests:
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served[:-1], np.int32)])
        pad = _bucket(len(seq))
        tokens = np.zeros((pad,), np.int32)
        tokens[:len(seq)] = seq           # tail pad: causal, never read
        xs.append(_embed(top, jnp.asarray(tokens), mult, st))
    for layer in range(cfg["num_layers"]):
        t = layer_tensors(layer)
        xs = [_layer(t, x, st) for x in xs]
        del t
    out = []
    for x, (prompt, served) in zip(xs, requests):
        n = len(served)
        pad = _bucket(n)                  # one program per bucket, not per n
        rows = np.zeros((pad,), np.int32)
        rows[:n] = len(prompt) - 1 + np.arange(n)
        tok = np.zeros((pad,), np.int32)
        tok[:n] = served
        gaps = _gaps(top, x, jnp.asarray(rows), jnp.asarray(tok), st)
        out.append(np.asarray(gaps)[:n])
    return out
