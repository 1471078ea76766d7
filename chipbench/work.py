"""Operations and bytes the model's work needs, from its shapes.

These count what the algorithm requires, whatever route or fusion runs
it: DBB weights count their non-zero multiply-adds and their packed bytes
(``nnz`` values of the value type plus one mask byte per block of
``block``), attention counts each token's real context (capped by the
sliding window), and the head counts dense. The per-layer metrics build on
them; the arithmetic follows ``roofline/analysis.py`` of the program,
copied here so that the yardstick stays with the benchmark.
"""
from __future__ import annotations

import numpy as np

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2, "int8": 1}


def projections(cfg: dict) -> list:
    """(name, K, N) of each DBB projection of one layer."""
    d, q = cfg["d_model"], cfg["num_heads"] * cfg["head_dim"]
    kv, ff = cfg["num_kv_heads"] * cfg["head_dim"], cfg["d_ff"]
    out = [("q", d, q), ("k", d, kv), ("v", d, kv), ("o", q, d),
           ("up", d, ff)]
    if cfg["mlp_gated"]:
        out.append(("gate", d, ff))
    return out + [("down", ff, d)]


def density(cfg: dict) -> float:
    return cfg["dbb"]["nnz"] / cfg["dbb"]["block"]


def dbb_flops(cfg: dict, m: int, k: int, n: int) -> float:
    """Non-zero multiply-adds of [m, k] x DBB [k, n], two ops each."""
    return 2.0 * m * k * n * density(cfg)


def dbb_weight_bytes(cfg: dict, k: int, n: int) -> float:
    blocks = k // cfg["dbb"]["block"] * n
    return blocks * (cfg["dbb"]["nnz"] * DTYPE_BYTES[cfg["dtype"]] + 1)


def dbb_gemm_bytes(cfg: dict, m: int, k: int, n: int) -> float:
    """Packed weight once, activations in and out."""
    a = DTYPE_BYTES[cfg["dtype"]]
    return dbb_weight_bytes(cfg, k, n) + a * m * (k + n)


def layer_matmul_flops(cfg: dict) -> float:
    """DBB projection ops of one token through one layer."""
    return sum(dbb_flops(cfg, 1, k, n) for _, k, n in projections(cfg))


def head_flops(cfg: dict) -> float:
    return 2.0 * cfg["d_model"] * cfg["vocab_size"]


def context_sum(first: int, last: int, window: int) -> float:
    """Sum over positions p = first..last of min(p + 1, window) (keys each
    position attends, window 0 meaning none)."""
    if last < first:
        return 0.0
    p = np.arange(first, last + 1, dtype=np.float64) + 1.0
    if window > 0:
        p = np.minimum(p, window)
    return float(p.sum())


def attention_flops_per_key(cfg: dict) -> float:
    """QK^T and PV of one query against one key, all heads of a layer."""
    return 4.0 * cfg["num_heads"] * cfg["head_dim"]


def request_flops(cfg: dict, prompt: int, served: int) -> float:
    """Model ops to serve one request greedily: every layer over the
    prompt and each served token but the last (the last is never fed
    back), the head once per served token."""
    if served <= 0:
        return 0.0
    tokens = prompt + served - 1
    ctx = context_sum(0, tokens - 1, cfg["sliding_window"])
    per_layer = (tokens * layer_matmul_flops(cfg)
                 + ctx * attention_flops_per_key(cfg))
    return cfg["num_layers"] * per_layer + served * head_flops(cfg)


def prefill_flops(cfg: dict, prompt: int) -> float:
    """Model ops of one request's prefill: every layer over the prompt,
    the head once for the first served token."""
    ctx = context_sum(0, prompt - 1, cfg["sliding_window"])
    per_layer = (prompt * layer_matmul_flops(cfg)
                 + ctx * attention_flops_per_key(cfg))
    return cfg["num_layers"] * per_layer + head_flops(cfg)


def decode_attention_work(cfg: dict, prompt: int, served: int) -> tuple:
    """(ops, bytes) of the decode steps' attention of one request: the
    step that feeds served token j (1-based, j < served) attends the
    prompt and j tokens; K and V of each real key are read once per step
    and layer, the query in and the output out."""
    if served <= 1:
        return 0.0, 0.0
    ctx = context_sum(prompt, prompt + served - 2, cfg["sliding_window"])
    a = DTYPE_BYTES[cfg["dtype"]]
    kv_row = 2 * cfg["num_kv_heads"] * cfg["head_dim"] * a
    q_row = 2 * cfg["num_heads"] * cfg["head_dim"] * a
    layers = cfg["num_layers"]
    ops = layers * ctx * attention_flops_per_key(cfg)
    nbytes = layers * (ctx * kv_row + (served - 1) * q_row)
    return ops, nbytes


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peaks: dict) -> tuple:
    """(percent of the roofline, bound): the least time the chip needs,
    the larger of ops over peak and bytes over bandwidth, over the time
    taken."""
    t_ops = ops / peaks["bf16_flops"]
    t_mem = nbytes / peaks["hbm_bytes_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
