"""Seeded weights of a configuration, made by the benchmark.

The program under test never makes its own weights here. This module draws
them from ``--seed`` on the device, in the type they are served in; the
harness lays them out as the program's param tree (`program_layer`) and
hands them to its packer, and the plain reference draws the very same
tensors again after the window, without anything the program made.

Every attention and MLP projection is DBB-sparse: exactly ``nnz`` non-zeros
in each block of ``block`` consecutive rows along K, at positions drawn
from the seed. Projection values are normal with std sqrt(block / (nnz K)),
so each projection's output has about unit variance.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the generator needs, read from a configuration file."""
    d: int
    hq: int
    hkv: int
    hd: int
    ff: int
    vocab: int
    layers: int
    gated: bool
    qkv_bias: bool
    norm: str
    tied: bool
    block: int
    nnz: int
    dtype: str
    embed_std: float
    head_std: float
    bias_std: float
    norm_std: float


def dims(cfg: dict) -> Dims:
    init = cfg["init"]
    return Dims(
        d=cfg["d_model"], hq=cfg["num_heads"], hkv=cfg["num_kv_heads"],
        hd=cfg["head_dim"], ff=cfg["d_ff"], vocab=cfg["vocab_size"],
        layers=cfg["num_layers"], gated=cfg["mlp_gated"],
        qkv_bias=cfg["qkv_bias"], norm=cfg["norm"],
        tied=cfg["tie_embeddings"], block=cfg["dbb"]["block"],
        nnz=cfg["dbb"]["nnz"], dtype=cfg["dtype"],
        embed_std=init["embed_std"], head_std=init["head_std"] or 0.0,
        bias_std=init["bias_std"], norm_std=init["norm_std"])


def root_key(seed: int) -> jax.Array:
    """Key of a run: every whole number up to 2**64 - 1 is its own seed."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jax.random.wrap_key_data(
        jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32))


def layer_key(seed: int, layer: int) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(root_key(seed), 1), layer)


def top_key(seed: int) -> jax.Array:
    return jax.random.fold_in(root_key(seed), 2)


def _dbb_matrix(key, k: int, n: int, dm: Dims) -> jax.Array:
    """[k, n] normal values with exactly nnz live entries per block."""
    kv, km = jax.random.split(key)
    w = jax.random.normal(kv, (k, n), jnp.float32) * math.sqrt(
        dm.block / (dm.nnz * k))
    r = jax.random.uniform(km, (k // dm.block, dm.block, n))
    i = jnp.arange(dm.block)
    ri, rj = r[:, :, None, :], r[:, None, :, :]
    # rank of each entry in its block, ties broken by position
    beats = (rj > ri) | ((rj == ri) & (i[None, None, :, None]
                                       < i[None, :, None, None]))
    keep = beats.sum(axis=2) < dm.nnz
    w = w.reshape(k // dm.block, dm.block, n) * keep
    return w.reshape(k, n).astype(dm.dtype)


def _vector(key, n: int, mean: float, std: float, dtype) -> jax.Array:
    return (mean + std * jax.random.normal(key, (n,), jnp.float32)).astype(
        dtype)


def _norm_params(key, dm: Dims) -> dict:
    ks, kb = jax.random.split(key)
    if dm.norm == "nonparam_ln":
        return {}
    p = {"scale": _vector(ks, dm.d, 1.0, dm.norm_std, dm.dtype)}
    if dm.norm == "layernorm":
        p["bias"] = _vector(kb, dm.d, 0.0, dm.norm_std, dm.dtype)
    return p


@functools.partial(jax.jit, static_argnums=1)
def layer_tensors(key, dm: Dims) -> dict:
    """One layer's tensors under the benchmark's own names."""
    ks = jax.random.split(key, 12)
    q, kv = dm.hq * dm.hd, dm.hkv * dm.hd
    t = {"wq": _dbb_matrix(ks[0], dm.d, q, dm),
         "wk": _dbb_matrix(ks[1], dm.d, kv, dm),
         "wv": _dbb_matrix(ks[2], dm.d, kv, dm),
         "wo": _dbb_matrix(ks[3], q, dm.d, dm),
         "w_up": _dbb_matrix(ks[4], dm.d, dm.ff, dm),
         "w_down": _dbb_matrix(ks[5], dm.ff, dm.d, dm),
         "ln1": _norm_params(ks[6], dm),
         "ln2": _norm_params(ks[7], dm)}
    if dm.gated:
        t["w_gate"] = _dbb_matrix(ks[8], dm.d, dm.ff, dm)
    if dm.qkv_bias:
        t["bq"] = _vector(ks[9], q, 0.0, dm.bias_std, dm.dtype)
        t["bk"] = _vector(ks[10], kv, 0.0, dm.bias_std, dm.dtype)
        t["bv"] = _vector(ks[11], kv, 0.0, dm.bias_std, dm.dtype)
    return t


@functools.partial(jax.jit, static_argnums=1)
def top_tensors(key, dm: Dims) -> dict:
    """Embedding table [vocab, d], final norm and, untied, head [d, vocab]."""
    ke, kh, kn = jax.random.split(key, 3)
    t = {"embed": (dm.embed_std * jax.random.normal(
            ke, (dm.vocab, dm.d), jnp.float32)).astype(dm.dtype),
         "final_norm": _norm_params(kn, dm)}
    if not dm.tied:
        t["head"] = (dm.head_std * jax.random.normal(
            kh, (dm.d, dm.vocab), jnp.float32)).astype(dm.dtype)
    return t


def program_layer(t: dict, dm: Dims) -> dict:
    """One layer laid out as the program's param tree
    (``models/transformer.py`` ``_layer_init``)."""
    def lin(w, b=None):
        return {"w": w} if b is None else {"w": w, "b": b}

    mlp = {"wi": lin(t["w_up"]), "wo": lin(t["w_down"])}
    if dm.gated:
        mlp["wg"] = lin(t["w_gate"])
    return {"attn": {"q_proj": lin(t["wq"], t.get("bq")),
                     "k_proj": lin(t["wk"], t.get("bk")),
                     "v_proj": lin(t["wv"], t.get("bv")),
                     "o_proj": lin(t["wo"])},
            "ln_attn": t["ln1"], "ln_mlp": t["ln2"], "mlp": mlp}


def program_params(seed: int, dm: Dims, pack) -> dict:
    """The program's packed param tree for ``seed``, built one layer at a
    time: each layer is drawn, laid out and passed through ``pack`` (the
    program's packer) in one jitted call, then written into the stacked
    tree, so no more than one dense layer is ever on the device."""
    def packed_layer(key):
        return pack(program_layer(layer_tensors(key, dm), dm))

    abstract = jax.eval_shape(packed_layer, layer_key(seed, 0))
    stacked = jax.jit(lambda: jax.tree.map(
        lambda a: jnp.zeros((dm.layers,) + a.shape, a.dtype), abstract))()

    @functools.partial(jax.jit, donate_argnums=0)
    def fill(stack, key, layer):
        return jax.tree.map(
            lambda s, n: jax.lax.dynamic_update_index_in_dim(s, n, layer, 0),
            stack, packed_layer(key))

    for layer in range(dm.layers):
        stacked = fill(stacked, layer_key(seed, layer), jnp.int32(layer))
    top = top_tensors(top_key(seed), dm)
    params = {"embed": {"table": top["embed"]}, "layers": stacked,
              "final_norm": top["final_norm"]}
    if not dm.tied:
        params["lm_head"] = {"w": top["head"]}
    return params
