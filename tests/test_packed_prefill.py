"""Padding-free packed batching + chunked prefill (DESIGN.md §12).

Ragged-traffic parity suite: the packed cu_seqlens admission path must be
token-identical to the legacy padded scheduler through the full serving
stack, on both KV backends, at every prefill chunk size — and the packed
flash kernel must never attend across request boundaries (oracle check
against the quadratic per-segment reference).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
    from hypothesis import given, settings

    hypothesis.settings.register_profile(
        "fast", max_examples=10, deadline=None)
    hypothesis.settings.load_profile("fast")
except ModuleNotFoundError:      # bare container: deterministic fallback
    from _hyp_fallback import given, settings, st

from repro.configs import get_config
from repro.kernels.attn.ops import packed_flash_attention
from repro.kernels.attn.ref import flash_prefill_ref, packed_prefill_ref
from repro.models import registry
from repro.serve.engine import ServeEngine


# ---------------------------------------------------------------------------
# kernel-level: block-diagonal masking oracle
# ---------------------------------------------------------------------------

def _rand(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _seg_ids(lens):
    return jnp.asarray(np.repeat(np.arange(len(lens)), lens), jnp.int32)


def _ragged_lens(seed, n_max=5, l_max=24):
    """Random length mixture that always includes a length-1 request and
    (at the top seeds) a bucket-max one — the two degenerate shapes the
    packed layout must survive."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, n_max + 1))
    lens = [int(rng.integers(1, l_max + 1)) for _ in range(n)]
    lens[0] = 1                      # degenerate: single-token request
    if seed % 2:
        lens[-1] = l_max             # degenerate: bucket-max request
    return lens


class TestPackedKernelOracle:
    @given(st.integers(0, 9))
    @settings(max_examples=10, deadline=None)
    def test_no_cross_request_attention(self, seed):
        """Packed kernel output, sliced per segment, equals the solo
        quadratic reference run on that segment alone — i.e. zero
        attention across request boundaries, for random ragged
        mixtures including len-1 and bucket-max rows."""
        lens = _ragged_lens(seed)
        t, hq, hkv, d = sum(lens), 4, 2, 16
        q = _rand((t, hq, d), seed)
        k = _rand((t, hkv, d), seed + 100)
        v = _rand((t, hkv, d), seed + 200)
        seg = _seg_ids(lens)
        got = packed_flash_attention(q, k, v, seg)
        off = 0
        for ln in lens:
            qs = q[None, off:off + ln]
            ks = k[None, off:off + ln]
            vs = v[None, off:off + ln]
            solo = flash_prefill_ref(
                jnp.moveaxis(qs, 2, 1), jnp.moveaxis(ks, 2, 1),
                jnp.moveaxis(vs, 2, 1), jnp.zeros((1, 1), jnp.int32),
                sm_scale=d ** -0.5)
            np.testing.assert_allclose(
                np.asarray(got[off:off + ln]),
                np.asarray(jnp.moveaxis(solo[0], 0, 1)),
                rtol=2e-5, atol=2e-5, err_msg=f"lens={lens} seg_len={ln}")
            off += ln

    @given(st.integers(0, 9))
    @settings(max_examples=10, deadline=None)
    def test_kernel_matches_packed_ref(self, seed):
        """Flash packed kernel vs the quadratic block-diagonal reference
        on the same concatenated layout."""
        lens = _ragged_lens(seed, l_max=33)
        t, hq, hkv, d = sum(lens), 4, 2, 16
        q = _rand((t, hq, d), seed + 1)
        k = _rand((t, hkv, d), seed + 101)
        v = _rand((t, hkv, d), seed + 201)
        seg = _seg_ids(lens)
        got = packed_flash_attention(q, k, v, seg, use_kernel=True)
        want = packed_flash_attention(q, k, v, seg, use_kernel=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"lens={lens}")

    def test_ref_is_block_diagonal(self):
        """The reference itself: perturbing one segment's keys must not
        change any other segment's output (oracle sanity)."""
        lens = [3, 1, 5]
        t, h, d = sum(lens), 2, 8
        q, k, v = (_rand((h, t, d), 7), _rand((h, t, d), 8),
                   _rand((h, t, d), 9))
        seg = _seg_ids(lens)
        base = packed_prefill_ref(q, k, v, seg, sm_scale=d ** -0.5)
        k2 = k.at[:, 3:4].add(100.0)       # clobber segment 1's only key
        v2 = v.at[:, 3:4].add(-50.0)
        pert = packed_prefill_ref(q, k2, v2, seg, sm_scale=d ** -0.5)
        np.testing.assert_array_equal(np.asarray(base[:, :3]),
                                      np.asarray(pert[:, :3]))
        np.testing.assert_array_equal(np.asarray(base[:, 4:]),
                                      np.asarray(pert[:, 4:]))
        assert not np.allclose(np.asarray(base[:, 3]),
                               np.asarray(pert[:, 3]))


# ---------------------------------------------------------------------------
# serve-level: packed == padded through the whole engine
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _engine(paged: bool):
    """One engine per backend, shared across examples — serve() takes
    prefill_mode/prefill_chunk per call, so jit caches amortize."""
    cfg = get_config("olmo-1b", smoke=True).replace(remat="none")
    if paged:
        cfg = cfg.replace(attn_impl="flash", kv_page_size=8)
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    return ServeEngine(cfg, params, max_batch=2)


def _prompts(seed, vocab, l_max=12):
    lens = _ragged_lens(seed, l_max=l_max)
    rng = np.random.default_rng(seed + 1000)
    prompts = [list(map(int, rng.integers(1, vocab - 1, size=ln)))
               for ln in lens]
    budgets = [int(b) for b in rng.integers(2, 7, size=len(lens))]
    return prompts, budgets


class TestServeParity:
    @given(st.integers(0, 6))
    @settings(max_examples=7, deadline=None)
    def test_packed_token_identical_to_padded(self, seed):
        """Random ragged mixtures (len-1 and bucket-max rows included):
        the packed scheduler's emitted tokens == the padded scheduler's,
        on both KV backends. (Backend loop lives inside the example so
        the property decorator composes with the fallback shim.)"""
        for paged in (False, True):
            eng = _engine(paged)
            prompts, budgets = _prompts(seed, eng.cfg.vocab_size)
            pad = eng.serve(prompts, budgets, prefill_mode="padded")
            got = eng.serve(prompts, budgets, prefill_mode="packed")
            assert got == pad, (paged, prompts, budgets)

    @pytest.mark.parametrize("paged", [False, True])
    def test_chunk_size_invariance(self, paged):
        """Chunked prefill must not change a single emitted token, for
        chunk ∈ {1, 7, page, smax} on both backends (whole-prompt packed
        call is the baseline)."""
        eng = _engine(paged)
        prompts, budgets = _prompts(3, eng.cfg.vocab_size, l_max=16)
        base = eng.serve(prompts, budgets, prefill_mode="packed",
                         prefill_chunk=0)
        smax = max(len(p) for p in prompts) + max(budgets)
        for chunk in (1, 7, 8, smax):
            got = eng.serve(prompts, budgets, prefill_mode="packed",
                            prefill_chunk=chunk)
            assert got == base, (chunk, prompts, budgets)

    def test_packed_matches_solo_generate(self):
        """Packed continuous batching vs one-request generate(): the
        end-to-end admission → prefill → decode chain is exact."""
        eng = _engine(False)
        prompts, budgets = _prompts(5, eng.cfg.vocab_size)
        served = eng.serve(prompts, budgets, prefill_mode="packed",
                           prefill_chunk=4)
        for p, bud, got in zip(prompts, budgets, served):
            solo = eng.generate([p], max_new_tokens=bud)[0]
            assert got == solo, (p, got, solo)

    def test_no_pad_tokens_charged(self):
        """The packed scheduler's stats must account every prompt token
        exactly once, and the per-call padding (bucket rounding only) must
        stay below the padded scheduler's rectangle."""
        eng = _engine(False)
        prompts, budgets = _prompts(2, eng.cfg.vocab_size, l_max=16)
        eng.serve(prompts, budgets, prefill_mode="packed")
        stats = eng.serve_stats
        total = sum(len(p) for p in prompts)
        assert stats["prompt_tokens"] == total
        # padded admission charges max_batch * T_max per wave; packed pays
        # bucket-rounded total tokens — strictly less on a ragged mix
        t_max = max(len(p) for p in prompts)
        assert stats["packed_prefill_tokens"] < len(prompts) * t_max * 2
        assert all(len(t) == b for t, b in
                   zip(eng.serve(prompts, budgets), budgets))

    @pytest.mark.parametrize("paged", [False, True])
    def test_prefill_logits_match_routes_and_forward(self, paged):
        """`prefill_logits` (the packed prefill path with the head left as
        logits) agrees across the Pallas and XLA routes and with each
        prompt's solo full forward, on both engine configs of this file —
        the correctness probe the chip smoke test relies on."""
        eng = _engine(paged)
        cfg = eng.cfg.replace(gemm_impl="pallas")
        params = registry.init_params(jax.random.PRNGKey(0), cfg)
        prompts = [[5, 17, 3, 250, 99], [7, 12], [2, 9, 31, 44, 8, 61, 3]]
        pal = ServeEngine(cfg, params, max_batch=4).prefill_logits(prompts)
        xla = ServeEngine(cfg.replace(gemm_impl="xla"), params,
                          max_batch=4).prefill_logits(prompts)
        assert pal.shape == (len(prompts), cfg.vocab_size)
        np.testing.assert_allclose(pal, xla, rtol=1e-4, atol=1e-4)
        w = registry.lm_head_weight(params, cfg).astype(jnp.float32)
        for i, p in enumerate(prompts):
            h, _ = registry.forward(params, cfg.replace(gemm_impl="xla"),
                                    {"tokens": jnp.asarray([p], jnp.int32)})
            want = np.asarray(h[0, -1].astype(jnp.float32) @ w)
            np.testing.assert_allclose(xla[i], want, rtol=1e-4, atol=1e-4)

    def test_ttft_recorded(self):
        """serve_stats carries a TTFT sample per request (used by the
        packed-prefill benchmark's jitter sweep)."""
        eng = _engine(False)
        prompts, budgets = _prompts(4, eng.cfg.vocab_size)
        eng.serve(prompts, budgets, prefill_mode="packed", prefill_chunk=4)
        ttft = eng.serve_stats["ttft_s"]
        assert len(ttft) == len(prompts)
        assert all(t > 0 for t in ttft)
