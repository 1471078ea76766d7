"""Unified kernel dispatch (DESIGN.md §11): golden route table, forced-route
parity, override precedence, and the grep-clean model-layer contract."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig
from repro.core.dbb import pack_dbb
from repro.kernels import dispatch

SRC = os.path.join(os.path.dirname(__file__), "..", "src", "repro")


def _chosen(decisions):
    [name] = [d.name for d in decisions if d.chosen]
    return name


def _rand(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    if jnp.dtype(dtype) == jnp.int8:
        return jnp.asarray(rng.integers(-20, 21, shape), jnp.int8)
    return jnp.asarray(rng.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# golden route table
# ---------------------------------------------------------------------------

class TestGoldenRouteTable:
    # (m, k, n, dtype, packed, pallas, kwargs) -> expected route
    CASES = [
        # decode regime: skinny weight-streaming kernels
        (1,   256,  512, jnp.float32, False, True, {}, "skinny_sta"),
        (4,   256,  512, jnp.float32, False, True, {}, "skinny_sta"),
        (32,  256,  512, jnp.bfloat16, False, True, {}, "skinny_sta"),
        (8,   256,  512, jnp.int8,   False, True, {}, "skinny_sta"),
        (16,  256,  512, jnp.float32, True,  True, {}, "skinny_dbb"),
        (8,   256,  512, jnp.int8,   True,  True, {}, "skinny_dbb"),
        # prefill/train regime: M-tiled kernels
        (128, 256,  512, jnp.float32, False, True, {}, "sta"),
        (512, 256,  512, jnp.bfloat16, False, True, {}, "sta"),
        (256, 256,  512, jnp.float32, True,  True, {}, "dbb_packed"),
        (256, 256,  512, jnp.int8,   True,  True, {}, "dbb_packed"),
        # above the skinny gate but tiny: M-tiled still ties-and-wins
        (48,  256,  512, jnp.float32, False, True, {}, "sta"),
        # pinned block shapes opt out of skinny (legacy wrapper contract)
        (4,   256,  512, jnp.float32, False, True, {"pinned": True}, "sta"),
        # head GEMV hint: stream when skinny fits, XLA above the gate
        (4,   256, 8192, jnp.float32, False, True, {"gemv": True},
         "skinny_sta"),
        (48,  256, 8192, jnp.float32, False, True, {"gemv": True}, "xla"),
        # XLA route family (gemm_impl="xla" / live mesh)
        (4,   256,  512, jnp.float32, False, False, {}, "xla"),
        (256, 256,  512, jnp.float32, False, False, {}, "xla"),
        # packed weight but K not divisible by the DBB block: no DBB route
        (4,   252,  512, jnp.float32, True,  True, {}, "xla"),
    ]

    @pytest.mark.parametrize(
        "m,k,n,dtype,packed,pallas,kw,expected",
        CASES, ids=[c[-1] + f"_m{c[0]}k{c[1]}n{c[2]}" for c in CASES])
    def test_expected_route(self, m, k, n, dtype, packed, pallas, kw,
                            expected):
        decs = dispatch.explain("matmul", m=m, k=k, n=n, dtype=dtype,
                                packed=packed, pallas=pallas, **kw)
        assert _chosen(decs) == expected, dispatch.format_table(decs)

    def test_conv_routes(self):
        geom = dict(conv_geom=(2, 16, 16, 64, 3, 3, 1))
        decs = dispatch.explain("conv", m=2 * 16 * 16, k=3 * 3 * 64, n=128,
                                pallas=True, **geom)
        assert _chosen(decs) == "conv_sta"
        decs = dispatch.explain("conv", m=2 * 16 * 16, k=3 * 3 * 64, n=128,
                                packed=True, pallas=True, **geom)
        assert _chosen(decs) == "conv_dbb"
        decs = dispatch.explain("conv", m=2 * 16 * 16, k=3 * 3 * 64, n=128,
                                pallas=False, **geom)
        assert _chosen(decs) == "conv_xla"

    def test_conv_explain_without_geom(self):
        """explain('conv') without conv_geom must return a table (kernel
        routes inapplicable with a clear reason), not crash unpacking."""
        decs = dispatch.explain("conv", m=512, k=576, n=128, pallas=True)
        assert _chosen(decs) == "conv_xla"
        by = {d.name: d for d in decs}
        assert "conv_geom" in by["conv_sta"].reason

    def test_attention_routes(self):
        flash_cfg = ModelConfig(gemm_impl="pallas", dtype="float32")
        xla_cfg = ModelConfig(gemm_impl="xla")
        assert _chosen(dispatch.explain("attention", m=64, k=64, n=64,
                                        cfg=flash_cfg)) == "attn_flash"
        # flash off, short sequence: naive (chunked defers below 2 chunks)
        assert _chosen(dispatch.explain("attention", m=64, k=64, n=64,
                                        cfg=xla_cfg)) == "attn_naive"
        # flash off, long divisible sequence: chunked
        assert _chosen(dispatch.explain("attention", m=4096, k=64, n=4096,
                                        cfg=xla_cfg)) == "attn_chunked"
        # ragged per-row ladders exclude chunked
        decs = dispatch.explain("attention", m=4096, k=64, n=4096,
                                cfg=xla_cfg, ragged=True)
        assert _chosen(decs) == "attn_naive"

    def test_packed_route_charged_at_total_tokens(self):
        """Ragged 8:1 max:median mix (DESIGN.md §12): the packed
        cu_seqlens route is costed at the batch's real token count, while
        the padded cost model charges the B×T_max rectangle — on this mix
        the rectangle mis-ranks the same traffic by > 2×. Padded routes
        must also refuse the packed spec outright (block-diagonal masking
        is not optional)."""
        flash_cfg = ModelConfig(gemm_impl="pallas", dtype="float32")
        lens = [512] + [64] * 7
        total, b, t_max = sum(lens), len(lens), max(lens)
        packed = dispatch.explain("attention", m=total, k=64, n=total,
                                  cfg=flash_cfg, packed_seq=True)
        assert _chosen(packed) == "attn_packed_flash"
        by = {d.name: d for d in packed}
        for name in ("attn_flash", "attn_chunked", "attn_naive"):
            assert not by[name].applicable
            assert "packed" in by[name].reason
        # charged at total_tokens, not a padded rectangle
        assert by["attn_packed_flash"].flops == 4.0 * total * total * 64
        padded = dispatch.explain("attention", m=t_max, k=64, n=t_max,
                                  cfg=flash_cfg, batch=b)
        assert _chosen(padded) == "attn_flash"
        cost_packed = by["attn_packed_flash"].cost_s
        cost_padded = next(d for d in padded if d.chosen).cost_s
        assert cost_padded > 2.0 * cost_packed

    def test_decode_routes(self):
        flash_cfg = ModelConfig(gemm_impl="pallas", dtype="float32",
                                num_heads=4, num_kv_heads=4)
        assert dispatch.decode_attention_route(
            flash_cfg, group=1, head_dim=64, itemsize=4, page=8,
            smax=64) == "attn_decode_flash"
        # ring caches and unaligned pages fall back to the XLA softmax
        assert dispatch.decode_attention_route(
            flash_cfg, group=1, head_dim=64, itemsize=4, page=8, smax=64,
            ring=True) == "attn_decode_xla"
        assert dispatch.decode_attention_route(
            flash_cfg, group=1, head_dim=64, itemsize=4, page=8,
            smax=60) == "attn_decode_xla"
        xla_cfg = ModelConfig(gemm_impl="xla")
        assert dispatch.decode_attention_route(
            xla_cfg, group=1, head_dim=64, itemsize=4, page=8,
            smax=64) == "attn_decode_xla"

    def test_explain_reports_cost_terms(self):
        decs = dispatch.explain("matmul", m=4, k=256, n=512, pallas=True)
        assert {d.name for d in decs} == {"xla", "sta", "skinny_sta",
                                          "dbb_packed", "skinny_dbb",
                                          "dbb_packed_w4",
                                          "skinny_dbb_w4"}
        for d in decs:
            assert d.flops > 0 and d.bytes > 0
            assert d.cost_s == pytest.approx(max(d.compute_s, d.memory_s))
            if not d.applicable:
                assert d.reason
        # at M=4 both pad to the sublane: bytes tie and priority picks
        # skinny; the compressed weight stream strictly beats dense bytes
        by = {d.name: d for d in decs}
        assert by["skinny_sta"].bytes <= by["sta"].bytes
        assert by["skinny_dbb"].bytes < by["skinny_sta"].bytes
        # formatting smoke
        assert "skinny_sta" in dispatch.format_table(decs)


# ---------------------------------------------------------------------------
# forced-route parity: every applicable route computes the same thing
# ---------------------------------------------------------------------------

class TestForcedRouteParity:
    SHAPES = [(4, 64, 128), (17, 128, 256), (64, 64, 128)]

    @pytest.mark.parametrize("m,k,n", SHAPES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
    def test_dense_routes_match_auto(self, m, k, n, dtype):
        x = _rand((m, k), dtype, 0)
        w = _rand((k, n), dtype, 1)
        bias = jnp.asarray(np.random.default_rng(2).standard_normal(n),
                           jnp.float32)
        kw = dict(act="relu", pallas=True)
        auto = np.asarray(dispatch.matmul(x, w, bias, **kw))
        decs = dispatch.explain("matmul", m=m, k=k, n=n, dtype=dtype,
                                pallas=True)
        forced_names = [d.name for d in decs if d.applicable]
        assert "xla" in forced_names
        for name in forced_names:
            got = np.asarray(dispatch.matmul(x, w, bias, route=name, **kw))
            if jnp.dtype(dtype) == jnp.int8:
                np.testing.assert_array_equal(got, auto, err_msg=name)
            else:
                np.testing.assert_allclose(got, auto, rtol=2e-5, atol=2e-5,
                                           err_msg=name)

    @pytest.mark.parametrize("m", [4, 64])
    def test_packed_routes_match_auto(self, m):
        k, n = 128, 256
        x = _rand((m, k), jnp.float32, 0)
        w = np.asarray(_rand((k, n), jnp.float32, 1))
        p = pack_dbb(jnp.asarray(w), 8, 4)
        bias = jnp.ones((n,), jnp.float32)
        auto = np.asarray(dispatch.matmul(x, p, bias, act="relu",
                                          pallas=True))
        decs = dispatch.explain("matmul", m=m, k=k, n=n, packed=True,
                                pallas=True)
        for name in [d.name for d in decs if d.applicable]:
            got = np.asarray(dispatch.matmul(x, p, bias, act="relu",
                                             pallas=True, route=name))
            np.testing.assert_allclose(got, auto, rtol=2e-5, atol=2e-5,
                                       err_msg=name)

    def test_int8_scaled_packed_routes_match_auto(self):
        """INT8 deployment format (quantized values + per-channel scale):
        the forced xla route must keep the scale for the int32 epilogue,
        not dequantize-and-truncate the weights back to int8."""
        from repro.core.dbb import DbbWeight
        from repro.core.quant import quantize_weight

        k, n = 128, 256
        x = _rand((4, k), jnp.int8, 0)
        qw = quantize_weight(np.asarray(_rand((k, n), jnp.float32, 1)))
        p0 = pack_dbb(qw.q, 8, 4)
        p = DbbWeight(values=p0.values.astype(jnp.int8), indices=p0.indices,
                      bitmask=p0.bitmask, scale=qw.scale, block=8, nnz=4,
                      k_dim=k)
        auto = np.asarray(dispatch.matmul(x, p, pallas=True))
        decs = dispatch.explain("matmul", m=4, k=k, n=n, dtype=jnp.int8,
                                packed=True, pallas=True)
        for name in [d.name for d in decs if d.applicable]:
            got = np.asarray(dispatch.matmul(x, p, pallas=True, route=name))
            np.testing.assert_array_equal(got, auto, err_msg=name)

    def test_conv_routes_match_auto(self):
        x = _rand((2, 8, 8, 16), jnp.float32, 0)
        w = _rand((3 * 3 * 16, 64), jnp.float32, 1)
        bias = jnp.ones((64,), jnp.float32)
        auto = np.asarray(dispatch.conv(x, w, bias, kh=3, kw=3, act="relu"))
        for name in ("conv_sta", "conv_xla"):
            got = np.asarray(dispatch.conv(x, w, bias, kh=3, kw=3,
                                           act="relu", route=name))
            np.testing.assert_allclose(got, auto, rtol=2e-5, atol=2e-5,
                                       err_msg=name)

    def test_caller_scale_folds_into_packed_routes(self):
        """A caller-supplied scale must reach the DBB kernels' epilogue
        (folded into the packed weight's scale), not be silently dropped."""
        m, k, n = 4, 128, 256
        x = _rand((m, k), jnp.float32, 0)
        p = pack_dbb(jnp.asarray(_rand((k, n), jnp.float32, 1)), 8, 4)
        scale = jnp.full((n,), 2.0, jnp.float32)
        want = np.asarray(dispatch.matmul(x, p, scale=scale, route="xla"))
        got = np.asarray(dispatch.matmul(x, p, scale=scale, pallas=True))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_inapplicable_forced_route_raises(self):
        x = _rand((4, 64), jnp.float32, 0)
        w = _rand((64, 128), jnp.float32, 1)
        with pytest.raises(ValueError, match="rejected"):
            dispatch.matmul(x, w, route="dbb_packed", pallas=True)


# ---------------------------------------------------------------------------
# override precedence: env var > kernel_routes > auto
# ---------------------------------------------------------------------------

class TestOverrides:
    def test_env_force_route(self, monkeypatch):
        monkeypatch.setenv(dispatch.FORCE_ROUTE_ENV, "xla")
        decs = dispatch.explain("matmul", m=4, k=256, n=512, pallas=True)
        assert _chosen(decs) == "xla"
        assert [d.forced for d in decs if d.chosen] == [True]

    def test_env_force_per_domain(self, monkeypatch):
        monkeypatch.setenv(dispatch.FORCE_ROUTE_ENV,
                           "matmul=sta,attention=attn_naive")
        assert _chosen(dispatch.explain("matmul", m=4, k=256, n=512,
                                        pallas=True)) == "sta"
        cfg = ModelConfig(gemm_impl="pallas", dtype="float32")
        assert _chosen(dispatch.explain("attention", m=64, k=64, n=64,
                                        cfg=cfg)) == "attn_naive"
        # other domains keep auto
        assert _chosen(dispatch.explain("conv", m=512, k=576, n=128,
                                        pallas=True,
                                        conv_geom=(2, 16, 16, 64, 3, 3, 1))
                       ) == "conv_sta"

    def test_cfg_kernel_routes(self):
        cfg = ModelConfig(gemm_impl="pallas",
                          kernel_routes=(("matmul", "xla"),))
        decs = dispatch.explain("matmul", m=4, k=256, n=512, cfg=cfg,
                                pallas=True)
        assert _chosen(decs) == "xla"

    def test_env_beats_cfg(self, monkeypatch):
        monkeypatch.setenv(dispatch.FORCE_ROUTE_ENV, "matmul=skinny_sta")
        cfg = ModelConfig(gemm_impl="pallas",
                          kernel_routes=(("matmul", "xla"),))
        decs = dispatch.explain("matmul", m=4, k=256, n=512, cfg=cfg,
                                pallas=True)
        assert _chosen(decs) == "skinny_sta"

    def test_rejected_force_falls_back_with_warning(self, monkeypatch):
        monkeypatch.setenv(dispatch.FORCE_ROUTE_ENV, "matmul=skinny_sta")
        dispatch._warned_forced.clear()
        with pytest.warns(UserWarning, match="falling back to auto"):
            # m=64 is outside the skinny gate -> guard rejects the force
            decs = dispatch.explain("matmul", m=64, k=256, n=512,
                                    pallas=True)
        assert _chosen(decs) == "sta"

    def test_bare_env_typo_warns(self, monkeypatch):
        monkeypatch.setenv(dispatch.FORCE_ROUTE_ENV, "skiny_sta")
        dispatch._warned_forced.clear()
        with pytest.warns(UserWarning, match="names no registered route"):
            decs = dispatch.explain("matmul", m=4, k=256, n=512,
                                    pallas=True)
        assert _chosen(decs) == "skinny_sta"     # auto still runs

    def test_cnn_kernel_routes_respected(self):
        """cnn_apply threads cfg into the conv domain, so kernel_routes
        pins reach it (numerics identical — the oracle route)."""
        from repro.configs import get_config
        from repro.models import registry
        from repro.models.cnn import cnn_apply

        cfg = get_config("convnet-dbb", smoke=True)
        params = registry.init_params(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (1, cfg.cnn_img, cfg.cnn_img, cfg.cnn_in_ch))
        y0 = cnn_apply(params, cfg, x, matmul="sta")
        cfg_pin = cfg.replace(kernel_routes=(("conv", "conv_xla"),
                                             ("matmul", "xla")))
        y1 = cnn_apply(params, cfg_pin, x, matmul="sta")
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=2e-4, atol=2e-4)

    def test_explain_attention_int8_matches_runtime(self):
        """explain() must not report flash for integer-dtype attention
        specs the runtime routes to the XLA paths."""
        cfg = ModelConfig(gemm_impl="pallas", dtype="float32")
        decs = dispatch.explain("attention", m=64, k=64, n=64,
                                dtype=jnp.int8, cfg=cfg)
        assert _chosen(decs) != "attn_flash"

    def test_forced_env_end_to_end_parity(self, monkeypatch):
        x = _rand((4, 64), jnp.float32, 0)
        w = _rand((64, 128), jnp.float32, 1)
        base = np.asarray(dispatch.matmul(x, w, pallas=True))
        monkeypatch.setenv(dispatch.FORCE_ROUTE_ENV, "matmul=xla")
        forced = np.asarray(dispatch.matmul(x, w, pallas=True))
        np.testing.assert_allclose(forced, base, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# model-layer integration
# ---------------------------------------------------------------------------

class TestRecordRoutes:
    def test_records_front_door_picks_only_inside_block(self):
        """`record_routes` collects the (domain, route) pairs the front
        doors pick while it is open; explain() tables and calls outside
        the block add nothing."""
        x = jnp.ones((8, 256), jnp.float32)
        w = jnp.ones((256, 128), jnp.float32)
        dispatch.matmul(x, w, pallas=True)
        with dispatch.record_routes() as log:
            dispatch.matmul(x, w, pallas=True)
            dispatch.matmul(x, w, pallas=False)
            dispatch.explain("matmul", m=512, k=256, n=128, pallas=True)
        dispatch.matmul(x, w, pallas=True)
        assert log == {("matmul", "skinny_sta"), ("matmul", "xla")}


class TestModelLayerIntegration:
    def test_kernel_routes_thread_through_model(self):
        """A config-pinned xla route changes nothing numerically for the
        model forward (the registry guarantees route interchangeability)."""
        from repro.configs import get_config
        from repro.models import registry

        cfg = get_config("olmo-1b", smoke=True).replace(
            remat="none", gemm_impl="pallas")
        params = registry.init_params(jax.random.PRNGKey(0), cfg)
        toks = jnp.asarray([[5, 17, 3, 250, 99, 7, 12, 2]], jnp.int32)
        h_auto, _ = registry.forward(params, cfg, {"tokens": toks})
        cfg_pin = cfg.replace(kernel_routes=(("matmul", "xla"),))
        h_pin, _ = registry.forward(params, cfg_pin, {"tokens": toks})
        np.testing.assert_allclose(np.asarray(h_auto), np.asarray(h_pin),
                                   rtol=2e-4, atol=2e-4)

    def test_moe_fused_experts_match_einsum(self):
        from repro.configs import get_config
        from repro.models import registry

        cfg = get_config("arctic-480b", smoke=True).replace(remat="none")
        params = registry.init_params(jax.random.PRNGKey(0), cfg)
        toks = jnp.asarray([[5, 17, 3, 250, 99, 7, 12, 2]], jnp.int32)
        h_xla, _ = registry.forward(params, cfg, {"tokens": toks})
        h_pal, _ = registry.forward(
            params, cfg.replace(gemm_impl="pallas"), {"tokens": toks})
        np.testing.assert_allclose(np.asarray(h_xla), np.asarray(h_pal),
                                   rtol=2e-3, atol=2e-3)

    def test_grep_clean_model_layer(self):
        """Acceptance contract: no direct kernel-subsystem imports outside
        the kernel package — all kernel selection flows through dispatch
        (DESIGN.md §11). Delegates to the repo-wide import-layering pass
        of the static verifier, which covers every repro/ module (the old
        grep here only saw models/ + core/dbb_linear.py)."""
        from repro.analysis import layering
        checked, violations = layering.check(os.path.dirname(SRC))
        assert checked > 0
        assert not violations, "\n".join(
            f"[{v.code}] {v.subject}: {v.message}" for v in violations)
