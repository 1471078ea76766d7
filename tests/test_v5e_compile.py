"""Compile the serving-path Pallas kernels for TPU v5e at real widths.

Every other test runs the kernels in interpret mode, which accepts block
shapes, shape casts and dtype conversions that Mosaic refuses. Here each
kernel is lowered and compiled for a described (not attached) v5e chip at
olmo-1b widths — d_model 2048, d_ff 8192, vocab 50304, head_dim 128,
16 KV heads — and the compiled program must hold the kernel
(``tpu_custom_call``). Nothing runs, so these say nothing about results
or speed; they stop a kernel the chip's compiler would refuse.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and test workers import every module.
"""
from __future__ import annotations

import os

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
    from repro.kernels.attn.ops import paged_decode_attention
    b, pages, page, n_log = 8, 64, 64, 8
    _compile(one_chip,
             lambda q, kp, vp, tab, ln, st: paged_decode_attention(
                 q, kp, vp, tab, ln, st, interpret=False),
             ((b, hkv, g, HEAD_DIM), BF16),
             ((pages, page, hkv, HEAD_DIM), BF16),
             ((pages, page, hkv, HEAD_DIM), BF16),
             ((b, n_log), I32), ((b,), I32), ((b,), I32))


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
