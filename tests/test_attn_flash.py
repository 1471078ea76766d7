"""Flash-attention kernel subsystem (DESIGN.md §10): parity matrix vs the
naive oracle (GQA/MQA × window × dtype × ragged), the structural
no-score-tensor trace assertion, VMEM-guard fallback, and the paged decode
kernel vs its gather-then-attend reference."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.attn.kernel import flash_prefill_pallas
from repro.kernels.attn.ops import (flash_attention, flash_ok,
                                    identity_block_table,
                                    paged_decode_attention)
from repro.kernels.attn.ref import flash_prefill_ref, paged_decode_ref
from repro.models import attention as attn_mod
from repro.models import registry


def _rand(shape, seed, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32).astype(dtype)


def _tol(dtype):
    return 3e-2 if dtype == jnp.bfloat16 else 1e-4


class TestFlashPrefillKernel:
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (4, 1)])
    @pytest.mark.parametrize("window", [0, 64])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_parity_matrix(self, hq, hkv, window, dtype):
        """GQA/MQA × sliding-window × dtype against the quadratic oracle
        (which materializes the full score tensor — the contrast is the
        point)."""
        b, t, d = 2, 128, 32
        q = _rand((b, t, hq, d), 0, dtype)
        k = _rand((b, t, hkv, d), 1, dtype)
        v = _rand((b, t, hkv, d), 2, dtype)
        got = flash_attention(q, k, v, window=window, block_q=32,
                              block_kv=32)
        want = flash_attention(q, k, v, window=window, use_kernel=False)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=_tol(dtype), atol=_tol(dtype))

    def test_ragged_left_pad_parity(self):
        """Per-row start offsets (left-padded serving batch): flash must
        mask pad keys exactly like _mask_bias; valid rows bit-compare to
        the oracle."""
        b, t, hq, hkv, d = 3, 64, 4, 2, 16
        q, k, v = (_rand((b, t, hq, d), 3), _rand((b, t, hkv, d), 4),
                   _rand((b, t, hkv, d), 5))
        start = jnp.asarray([0, 7, 33], jnp.int32)
        got = flash_attention(q, k, v, start, block_q=16, block_kv=16)
        want = flash_attention(q, k, v, start, use_kernel=False)
        for i, s0 in enumerate([0, 7, 33]):
            np.testing.assert_allclose(
                np.asarray(got[i, s0:]), np.asarray(want[i, s0:]),
                rtol=1e-5, atol=1e-5)

    def test_softcap_and_unaligned_lengths(self):
        """Logit softcap (applied pre-mask, like _scores) + T not divisible
        by the block grid (ops-layer padding)."""
        b, t, hq, d = 1, 45, 2, 16
        q, k, v = (_rand((b, t, hq, d), 6), _rand((b, t, hq, d), 7),
                   _rand((b, t, hq, d), 8))
        got = flash_attention(q, k, v, softcap=30.0, block_q=16,
                              block_kv=16)
        want = flash_attention(q, k, v, softcap=30.0, use_kernel=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_kernel_matches_standalone_ref(self):
        """Head-major kernel entry point against ref (no ops wrapper)."""
        q = _rand((1, 2, 32, 16), 9)
        k = _rand((1, 2, 32, 16), 10)
        v = _rand((1, 2, 32, 16), 11)
        got = flash_prefill_pallas(q, k, v, sm_scale=0.25, block_q=16,
                                   block_kv=16, interpret=True)
        want = flash_prefill_ref(q, k, v, sm_scale=0.25)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_vmem_guard(self):
        assert flash_ok(128, 128, 128, 4)
        assert flash_ok(4096, 4096, 256, 2)
        # pathological head dim: even the minimal block pair blows VMEM
        assert not flash_ok(128, 128, 1 << 20, 4)


class TestPagedDecodeKernel:
    @pytest.mark.parametrize("g", [1, 2, 4])
    @pytest.mark.parametrize("window", [0, 5])
    def test_matches_gather_ref(self, g, window):
        """Block-table gather + online softmax == gather-then-attend."""
        b, hkv, d, pool, page, n_log = 2, 2, 16, 9, 4, 3
        q = _rand((b, hkv, g, d), 12)
        kp = _rand((pool, page, hkv, d), 13)
        vp = _rand((pool, page, hkv, d), 14)
        tab = jnp.asarray([[5, 1, 7], [8, 3, 0]], jnp.int32)
        lengths = jnp.asarray([9, 4], jnp.int32)
        start = jnp.asarray([2, 0], jnp.int32)
        got = paged_decode_attention(q, kp, vp, tab, lengths, start,
                                     window=window)
        want = paged_decode_attention(q, kp, vp, tab, lengths, start,
                                      window=window, use_kernel=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("g", [1, 4], ids=["mha", "gqa"])
    @pytest.mark.parametrize("layer", [0, 2], ids=["first", "last"])
    def test_stacked_pool_layer_matches_ref(self, g, layer):
        """The kernel over pools stacked over L layers, at a traced layer
        index, equals the gather reference on that layer's pool alone —
        the first and the last layer, MHA and GQA, one row whose table
        sits wholly on the dummy page 0."""
        n_layers, b, hkv, d, pool, page, n_log = 3, 3, 2, 16, 9, 8, 3
        q = _rand((b, hkv, g, d), 20)
        kp = _rand((n_layers, pool, page, hkv, d), 21)
        vp = _rand((n_layers, pool, page, hkv, d), 22)
        tab = jnp.asarray([[5, 1, 7], [8, 3, 0], [0, 0, 0]], jnp.int32)
        lengths = jnp.asarray([17, 9, 5], jnp.int32)
        start = jnp.asarray([2, 0, 0], jnp.int32)
        got = jax.jit(lambda ly: paged_decode_attention(
            q, kp, vp, tab, lengths, start, layer=ly))(jnp.int32(layer))
        want = paged_decode_ref(q, kp[layer], vp[layer], tab, lengths,
                                start, sm_scale=1.0 / math.sqrt(d))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_identity_table_is_contiguous(self):
        """A contiguous [B, S, H, D] cache reshaped to pages under the
        identity table attends identically to the raw layout."""
        b, s, hkv, g, d, page = 2, 16, 2, 2, 16, 4
        n_log = s // page
        kc = _rand((b, s, hkv, d), 15)
        vc = _rand((b, s, hkv, d), 16)
        q = _rand((b, hkv, g, d), 17)
        lengths = jnp.asarray([10, 15], jnp.int32)
        start = jnp.zeros((b,), jnp.int32)
        tab = identity_block_table(b, n_log)
        kp = kc.reshape(b * n_log, page, hkv, d)
        vp = vc.reshape(b * n_log, page, hkv, d)
        got = paged_decode_attention(q, kp, vp, tab, lengths, start)
        want = paged_decode_attention(q, kp, vp, tab, lengths, start,
                                      use_kernel=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# model-level dispatch
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_lm():
    cfg = get_config("olmo-1b", smoke=True).replace(remat="none")
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


class TestModelDispatch:
    def test_forward_flash_matches_default(self, small_lm):
        cfg, params = small_lm
        toks = jnp.asarray([[5, 17, 3, 250, 99, 7, 12, 2]], jnp.int32)
        h0, _ = registry.forward(params, cfg, {"tokens": toks})
        h1, _ = registry.forward(params, cfg.replace(attn_impl="flash"),
                                 {"tokens": toks})
        np.testing.assert_allclose(np.asarray(h0, np.float32),
                                   np.asarray(h1, np.float32),
                                   rtol=5e-4, atol=5e-4)

    def test_auto_routes_flash_on_pallas_route(self, small_lm):
        """attn_impl='auto' + gemm_impl='pallas' (single device) must pick
        the flash backend — naive stays the use_kernel=False oracle only."""
        cfg, _ = small_lm
        assert attn_mod._flash_backend(cfg.replace(gemm_impl="pallas"))
        assert attn_mod._flash_backend(cfg.replace(attn_impl="flash"))
        assert not attn_mod._flash_backend(cfg)          # xla route: auto off
        assert not attn_mod._flash_backend(
            cfg.replace(gemm_impl="pallas", attn_impl="chunked"))

    def test_ragged_prefill_flash_matches_naive(self, small_lm):
        """Left-padded ragged batch through the flash backend must match
        the naive ragged path on every non-pad position."""
        cfg, params = small_lm
        toks = jnp.asarray([[0, 0, 0, 5, 17, 3, 250, 99],
                            [9, 9, 9, 9, 1, 2, 7, 3]], jnp.int32)
        start = jnp.asarray([3, 0], jnp.int32)
        h0, c0 = registry.prefill(params, cfg, tokens=toks,
                                  cache=registry.init_cache(cfg, 2, 12),
                                  start=start)
        cfgf = cfg.replace(attn_impl="flash")
        h1, c1 = registry.prefill(params, cfgf, tokens=toks,
                                  cache=registry.init_cache(cfgf, 2, 12),
                                  start=start)
        np.testing.assert_allclose(np.asarray(h0[0, 3:], np.float32),
                                   np.asarray(h1[0, 3:], np.float32),
                                   rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(np.asarray(h0[1], np.float32),
                                   np.asarray(h1[1], np.float32),
                                   rtol=5e-4, atol=5e-4)

    def test_guard_falls_back_to_chunked(self, small_lm, monkeypatch):
        """When the VMEM guard rejects the call, attn_impl='flash' must
        degrade to the XLA paths, not crash."""
        cfg, params = small_lm
        # the VMEM guard lives in the dispatch registry's attn_flash route
        # (DESIGN.md §11), which reads flash_ok from the attn ops module
        monkeypatch.setattr("repro.kernels.attn.ops.flash_ok",
                            lambda *a, **k: False)
        toks = jnp.asarray([[5, 17, 3, 250]], jnp.int32)
        h0, _ = registry.forward(params, cfg, {"tokens": toks})
        h1, _ = registry.forward(params, cfg.replace(attn_impl="flash"),
                                 {"tokens": toks})
        np.testing.assert_allclose(np.asarray(h0, np.float32),
                                   np.asarray(h1, np.float32),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# structural: the score tensor never materializes
# ---------------------------------------------------------------------------

class TestNoScoreTensor:
    B, T, HQ, HKV, D = 2, 256, 4, 2, 32

    def _peak(self, cfg):
        from repro.analysis.materialize import max_intermediate_elems
        q = jnp.zeros((self.B, self.T, self.HQ, self.D))
        k = jnp.zeros((self.B, self.T, self.HKV, self.D))
        v = jnp.zeros((self.B, self.T, self.HKV, self.D))
        pos = jnp.arange(self.T)[None, :]
        return max_intermediate_elems(
            lambda *a: attn_mod._attention_core(*a, cfg), q, k, v, pos)

    def test_flash_never_materializes_scores(self, small_lm):
        """Trace-time assertion via the shared repro.analysis walker: no
        intermediate in the flash route is as large as the [B, Hq, T, T]
        score tensor; the naive oracle (control) materializes exactly
        that."""
        cfg, _ = small_lm
        score_elems = self.B * self.HQ * self.T * self.T
        flash_max = self._peak(cfg.replace(attn_impl="flash"))
        naive_max = self._peak(cfg.replace(attn_impl="naive"))
        assert flash_max < score_elems, (
            f"flash route materialized a {flash_max}-element tensor "
            f"(score tensor would be {score_elems})")
        assert naive_max >= score_elems     # control: oracle really does
