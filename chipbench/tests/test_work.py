"""Operation and byte counts against hand counts at olmo-1b shapes."""
import json
import os

import pytest

from chipbench import work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def olmo():
    with open(os.path.join(HERE, "configs", "olmo-1b-dbb.json")) as f:
        return json.load(f)


def test_projections(olmo):
    assert work.projections(olmo) == [
        ("q", 2048, 2048), ("k", 2048, 2048), ("v", 2048, 2048),
        ("o", 2048, 2048), ("up", 2048, 8192), ("gate", 2048, 8192),
        ("down", 8192, 2048)]


def test_dbb_weight_bytes(olmo):
    # 2048 x 8192 weight: 256 x 8192 blocks of 8; 4 bf16 values + 1 mask
    # byte per block = 9 bytes per 8 weights = 1.125 B/weight
    assert work.dbb_weight_bytes(olmo, 2048, 8192) == 2048 * 8192 * 1.125
    # one layer: 4 d^2 + 3 d ff weights at 1.125 B
    layer = sum(work.dbb_weight_bytes(olmo, k, n)
                for _, k, n in work.projections(olmo))
    assert layer == (4 * 2048 ** 2 + 3 * 2048 * 8192) * 1.125


def test_dbb_flops(olmo):
    # 32 rows against 2048 x 8192 at 4 of 8 live: 2 * 32 * 2048 * 8192 / 2
    assert work.dbb_flops(olmo, 32, 2048, 8192) == 32 * 2048 * 8192
    assert work.layer_matmul_flops(olmo) == 4 * 2048 ** 2 + 3 * 2048 * 8192


def test_gemm_bytes(olmo):
    m, k, n = 32, 2048, 8192
    assert work.dbb_gemm_bytes(olmo, m, k, n) == (
        k * n * 1.125 + 2 * m * k + 2 * m * n)


def test_context_sum():
    assert work.context_sum(0, 3, 0) == 1 + 2 + 3 + 4
    assert work.context_sum(0, 5, 3) == 1 + 2 + 3 + 3 + 3 + 3
    assert work.context_sum(4, 3, 0) == 0


def test_request_flops(olmo):
    # a 10-token prompt and 3 served tokens: 12 token-passes through 16
    # layers, attention over 1 + 2 + ... + 12 keys, the head 3 times
    per_layer = 12 * (4 * 2048 ** 2 + 3 * 2048 * 8192) \
        + 78 * 4 * 16 * 128
    head = 2 * 2048 * 50304
    assert work.request_flops(olmo, 10, 3) == 16 * per_layer + 3 * head
    assert work.request_flops(olmo, 10, 0) == 0


def test_decode_attention_work(olmo):
    # 10-token prompt, 3 served: decode steps feed tokens 1 and 2, which
    # attend 11 and 12 keys; K and V rows are 2 * 16 * 128 * 2 bytes
    ops, nbytes = work.decode_attention_work(olmo, 10, 3)
    assert ops == 16 * 23 * 4 * 16 * 128
    assert nbytes == 16 * (23 * 2 * 16 * 128 * 2 + 2 * 2 * 16 * 128 * 2)
    assert work.decode_attention_work(olmo, 10, 1) == (0.0, 0.0)


def test_roofline_share():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    assert work.roofline_share(100.0, 5.0, 2.0, peaks) == (50.0, "compute")
    assert work.roofline_share(10.0, 50.0, 10.0, peaks) == (50.0, "memory")
