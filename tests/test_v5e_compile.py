"""Compile the serving-path Pallas kernels for TPU v5e at real widths.

Every other test runs the kernels in interpret mode, which accepts block
shapes, shape casts and dtype conversions that Mosaic refuses. Here each
kernel is lowered and compiled for a described (not attached) v5e chip at
olmo-1b widths — d_model 2048, d_ff 8192, vocab 50304, head_dim 128,
16 KV heads — and the compiled program must hold the kernel
(``tpu_custom_call``). Nothing runs, so these say nothing about results
or speed; they stop a kernel the chip's compiler would refuse. The last
tests compile whole paged serving steps and read their HLO and memory
analysis: the K/V pool is written in place, never sliced or copied.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and test workers import every module.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest

D_MODEL, D_FF, VOCAB, HEAD_DIM, KV_HEADS = 2048, 8192, 50304, 128, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Lower + compile ``fn`` for the described chip; return the HLO text
    after asserting the Pallas kernel survived into it."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.mark.parametrize("m,skinny", [(512, False), (8, True)],
                         ids=["sta", "skinny_sta"])
def test_sta_gemm(one_chip, m, skinny):
    from repro.kernels.sta_gemm.ops import sta_gemm
    _compile(one_chip,
             lambda x, w: sta_gemm(x, w, interpret=False, skinny=skinny,
                                   autotune=False),
             ((m, D_MODEL), BF16), ((D_MODEL, D_FF), BF16))


@pytest.mark.parametrize("m,skinny,plane", [
    (512, False, BF16), (512, False, I8), (8, True, BF16), (8, True, I8)],
    ids=["dbb_packed-bf16", "dbb_packed-int8", "skinny_dbb-bf16",
         "skinny_dbb-int8"])
def test_dbb_gemm(one_chip, m, skinny, plane):
    from repro.kernels.dbb_gemm.ops import dbb_gemm
    x_dtype = I8 if plane == I8 else BF16
    nb = D_MODEL // 8
    _compile(one_chip,
             lambda x, v, mk: dbb_gemm(x, v, mk, interpret=False,
                                       skinny=skinny, autotune=False),
             ((m, D_MODEL), x_dtype), ((nb * 4, D_FF), plane),
             ((nb, D_FF), I32))


@pytest.mark.parametrize("m,skinny", [(512, False), (8, True)],
                         ids=["dbb_packed_w4", "skinny_dbb_w4"])
def test_dbb_gemm_w4(one_chip, m, skinny):
    from repro.kernels.dbb_gemm.ops import dbb_gemm
    nb, group = D_MODEL // 8, 128
    _compile(one_chip,
             lambda x, v, mk, gs: dbb_gemm(x, v, mk, interpret=False,
                                           skinny=skinny, bits=4,
                                           group=group, gscale=gs),
             ((m, D_MODEL), BF16), ((nb * 2, D_FF), I8), ((nb, D_FF), I32),
             ((D_MODEL // group, D_FF), F32))


def test_flash_prefill(one_chip):
    from repro.kernels.attn.ops import flash_attention
    b, t, hq = 2, 512, 16
    _compile(one_chip,
             lambda q, k, v, st: flash_attention(q, k, v, start=st,
                                                 interpret=False,
                                                 autotune=False),
             ((b, t, hq, HEAD_DIM), BF16), ((b, t, KV_HEADS, HEAD_DIM), BF16),
             ((b, t, KV_HEADS, HEAD_DIM), BF16), ((b,), I32))


def test_packed_prefill(one_chip):
    from repro.kernels.attn.ops import packed_flash_attention
    t, hq = 1024, 16
    _compile(one_chip,
             lambda q, k, v, seg: packed_flash_attention(q, k, v, seg,
                                                         interpret=False),
             ((t, hq, HEAD_DIM), BF16), ((t, KV_HEADS, HEAD_DIM), BF16),
             ((t, KV_HEADS, HEAD_DIM), BF16), ((t,), I32))


@pytest.mark.parametrize("hkv,g", [(KV_HEADS, 1), (2, 5)],
                         ids=["mha", "gqa"])
def test_paged_decode(one_chip, hkv, g):
    """Both forms of the one kernel: one layer's [P, page, Hkv, D] pool,
    and the serving cache's [L, P, page, Hkv, D] stack read at a traced
    layer index."""
    from repro.kernels.attn.ops import paged_decode_attention
    b, layers, pages, page, n_log = 8, 16, 64, 64, 8
    for pool in ((pages, page, hkv, HEAD_DIM),
                 (layers, pages, page, hkv, HEAD_DIM)):
        _compile(one_chip,
                 lambda q, kp, vp, tab, ln, st, ly: paged_decode_attention(
                     q, kp, vp, tab, ln, st, layer=ly[0], interpret=False),
                 ((b, hkv, g, HEAD_DIM), BF16), (pool, BF16), (pool, BF16),
                 ((b, n_log), I32), ((b,), I32), ((b,), I32), ((1,), I32))


def test_head_sample_fused(one_chip):
    from repro.kernels.sample.ops import head_sample_fused
    b = 8
    _compile(one_chip,
             lambda h, w, c, t, r, p, f, s, st: head_sample_fused(
                 h, w, c, t, r, p, f, s, st, interpret=False),
             ((b, D_MODEL), F32), ((D_MODEL, VOCAB), BF16), ((b, VOCAB), I32),
             ((b,), F32), ((b,), F32), ((b,), F32), ((b,), F32), ((b,), I32),
             ((b,), I32))


@pytest.mark.parametrize("dbb", [False, True], ids=["conv_sta", "conv_dbb"])
def test_conv_gemm(one_chip, dbb):
    from repro.kernels.conv_gemm.ops import conv_gemm, conv_gemm_dbb
    b, hw, c, n, k = 8, 32, 64, 128, 3
    x = ((b, hw, hw, c), BF16)
    if dbb:
        nb = k * k * c // 8
        _compile(one_chip,
                 lambda x, v, mk: conv_gemm_dbb(x, v, mk, kh=k, kw=k,
                                                interpret=False,
                                                autotune=False),
                 x, ((nb * 4, n), BF16), ((nb, n), I32))
    else:
        _compile(one_chip,
                 lambda x, w: conv_gemm(x, w, kh=k, kw=k, interpret=False,
                                        autotune=False),
                 x, ((k * k * c, n), BF16))


# ---------------------------------------------------------------------------
# the paged serving steps write the K/V pool in place
# ---------------------------------------------------------------------------

# Two layers and a pool big enough that one stacked pool outweighs every
# other temporary of a step (the head's f32 copy of the tied table is
# 412 MB): a pool-sized temporary cannot hide among them.
POOL_LAYERS, POOL_PAGES, PAGE, SLOTS = 2, 2048, 64, 32
POOL_SHAPE = (POOL_LAYERS, POOL_PAGES, PAGE, KV_HEADS, HEAD_DIM)
POOL_BYTES = POOL_LAYERS * POOL_PAGES * PAGE * KV_HEADS * HEAD_DIM * 2

_HLO_OP = re.compile(r"= \w+\[([0-9,]*)\](?:\{[^}]*\})? "
                     r"(copy|dynamic-slice|dynamic-update-slice)\(")


def _pool_traffic(compiled):
    """Whatever of the K/V pool a compiled step moves besides its new
    rows: each copy, dynamic-slice or dynamic-update-slice whose result is
    one layer's pool or the stacked pool (unit dims aside), and a
    temporary allocation as large as one stacked pool."""
    pool_shapes = {POOL_SHAPE, POOL_SHAPE[1:]}
    found = []
    for m in _HLO_OP.finditer(compiled.as_text()):
        dims = tuple(int(x) for x in m.group(1).split(",") if x)
        if tuple(x for x in dims if x != 1) in pool_shapes:
            found.append(f"{m.group(2)} {dims}")
    temp = compiled.memory_analysis().temp_size_in_bytes
    if temp >= POOL_BYTES:
        found.append(f"temporaries of {temp} bytes")
    return found


def _serving_shapes(one_chip):
    """olmo-1b at its widths with two DBB-packed layers: (cfg, params,
    paged cache) as shapes on the described chip."""
    from repro.config import DbbConfig
    from repro.configs import get_config
    from repro.core.dbb_linear import pack_tree
    from repro.models import registry
    from repro.serve.kv_cache import init_paged_cache
    cfg = get_config("olmo-1b").replace(
        num_layers=POOL_LAYERS, param_dtype="bfloat16", gemm_impl="pallas",
        kv_page_size=PAGE, dbb=DbbConfig(enabled=True, block=8, nnz=4))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda k: pack_tree(registry.init_params(k, cfg), cfg.dbb),
        jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: init_paged_cache(cfg, SLOTS, POOL_PAGES, PAGE, 16)))
    return cfg, params, cache


@pytest.fixture
def chip_kernels(monkeypatch):
    """Whole serving steps built for the described chip: the kernels'
    ops modules lower for Mosaic instead of the CPU interpreter."""
    import repro.kernels.attn.ops as attn_ops
    import repro.kernels.dbb_gemm.ops as dbb_ops
    import repro.kernels.sample.ops as sample_ops
    import repro.kernels.sta_gemm.ops as sta_ops
    for mod in (attn_ops, dbb_ops, sample_ops, sta_ops):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)


@pytest.mark.parametrize("step", ["decode_chunk", "packed_prefill"])
def test_paged_step_writes_pool_in_place(one_chip, chip_kernels, step):
    """The engine's greedy decode chunk and its packed prefill step, jitted
    as `ServeEngine` jits them (cache donated): the layer loop carries the
    stacked pool, so neither slices a layer's pool out, writes it back,
    nor copies it, and no temporary holds a second pool."""
    from repro.serve.engine import (make_decode_chunk, make_decode_step,
                                    make_packed_prefill_step)
    cfg, params, cache = _serving_shapes(one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=one_chip)

    if step == "decode_chunk":
        fn = make_decode_chunk(make_decode_step(cfg), 1, 8)
        args = (params, cache, i32(SLOTS),
                jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=one_chip))
    else:
        t = 1024
        fn = make_packed_prefill_step(cfg)
        args = (params, cache, i32(1, t), i32(t), i32(1, t), i32(t), i32(t),
                i32(SLOTS))
    compiled = jax.jit(fn, donate_argnums=1).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _pool_traffic(compiled) == []


def test_pool_check_catches_xs_ys_scan(one_chip, chip_kernels):
    """Control for the check above: the layer loop that hands each layer
    its pool as a scan input and returns it as a scan output (a plain
    ``lax.scan`` over the stack, the decode kernel on each sliced-out
    layer) must be caught slicing and writing back whole pools."""
    from repro.kernels.attn.ops import paged_decode_attention

    def xs_ys(k_pages, v_pages, q, table, lengths, new_kv):
        phys, off = table[:, 0], lengths % PAGE

        def layer(acc, kv):
            kp = kv[0].at[phys, off].set(new_kv)
            vp = kv[1].at[phys, off].set(new_kv)
            o = paged_decode_attention(q, kp, vp, table, lengths)
            return acc + o.astype(F32).sum(), (kp, vp)

        return jax.lax.scan(layer, jnp.zeros((), F32), (k_pages, v_pages))

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        (POOL_SHAPE, BF16), (POOL_SHAPE, BF16),
        ((SLOTS, KV_HEADS, 1, HEAD_DIM), BF16), ((SLOTS, 16), I32),
        ((SLOTS,), I32), ((SLOTS, KV_HEADS, HEAD_DIM), BF16))]
    compiled = jax.jit(xs_ys, donate_argnums=(0, 1)).lower(*args).compile()
    found = _pool_traffic(compiled)
    assert any(f.startswith("dynamic-slice") for f in found), found
    assert any(f.startswith("dynamic-update-slice") for f in found), found
