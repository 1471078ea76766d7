"""Paged KV cache serving (DESIGN.md §10): allocator invariants, paged
decode bit-equivalence with the contiguous flash cache under mid-stream
admission/retirement, page recycling under pool pressure."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import registry
from repro.serve.engine import ServeEngine
from repro.serve.kv_cache import (DUMMY_PAGE, PageAllocator, init_paged_cache,
                                  pages_needed)
from repro.serve.sampling import SamplingParams


class TestPageAllocator:
    def test_alloc_free_roundtrip(self):
        a = PageAllocator(8)                 # pages 1..7 usable
        assert a.free_pages == 7
        got = a.alloc(3)
        assert len(got) == 3 and DUMMY_PAGE not in got
        assert a.free_pages == 4 and a.used_pages == 3
        a.free(got)
        assert a.free_pages == 7

    def test_exhaustion_defers(self):
        a = PageAllocator(4)
        assert a.alloc(3) is not None
        assert a.alloc(1) is None            # nothing left: caller defers
        assert a.free_pages == 0

    def test_dummy_never_handed_out(self):
        a = PageAllocator(16)
        seen = a.alloc(15)
        assert a.alloc(1) is None
        assert DUMMY_PAGE not in seen and len(set(seen)) == 15

    def test_pages_needed(self):
        assert pages_needed(8, 8, 8) == 2    # prompt fills p0, decode p1
        assert pages_needed(8, 9, 8) == 3
        assert pages_needed(3, 1, 8) == 1
        assert pages_needed(0, 1, 8) == 1


def test_init_paged_cache_shapes():
    cfg = get_config("olmo-1b", smoke=True)
    c = init_paged_cache(cfg, n_slots=3, pool_pages=9, page=8, n_log=4)
    L, hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    assert c["k_pages"].shape == (L, 9, 8, hkv, hd)
    assert c["block_table"].shape == (3, 4)
    assert c["block_table"].dtype == jnp.int32
    assert c["length"].shape == (3,) and c["start"].shape == (3,)


# ---------------------------------------------------------------------------
# serving parity: the ragged continuous-batching suite, paged vs contiguous
# ---------------------------------------------------------------------------

PROMPTS = [[5, 17, 3], [9, 9, 9, 9, 1, 2], [42, 7, 13, 250, 99],
           [4, 8], [100, 200, 300]]
BUDGETS = [6, 3, 8, 5, 4]


@pytest.fixture(scope="module")
def flash_lm():
    """Flash backend + page-8 decode tiles — both engines below run the
    SAME decode kernel in the same page-visit order; only the physical
    page layout differs, which is what makes the comparison bit-exact."""
    cfg = get_config("olmo-1b", smoke=True).replace(
        remat="none", attn_impl="flash", kv_page_size=8)
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def paged_outputs(flash_lm):
    cfg, params = flash_lm
    eng = ServeEngine(cfg, params, max_batch=2, fetch_chunk=3)
    outs = eng.serve(PROMPTS, max_new_tokens=BUDGETS)
    return eng, outs


class TestPagedServing:
    def test_bit_identical_to_contiguous(self, flash_lm, paged_outputs):
        """More requests than slots (mid-stream admission + retirement):
        the paged scheduler must emit exactly the contiguous flash
        engine's tokens — same kernel, identity block table vs real block
        table."""
        cfg, params = flash_lm
        _, out_paged = paged_outputs
        eng_c = ServeEngine(cfg, params, max_batch=2, fetch_chunk=3,
                            paged=False)
        out_contig = eng_c.serve(PROMPTS, max_new_tokens=BUDGETS)
        assert out_paged == out_contig

    @pytest.mark.parametrize("path", ["early_exit", "sampled",
                                      "chunk_continuation"])
    def test_paged_paths_bit_identical_to_contiguous(self, flash_lm, path):
        """Each step that carries the stacked pool through its layer loop
        emits the contiguous engine's tokens: a greedy chunk in which every
        row finishes mid-chunk (the early exit skips the chunk's other
        steps), a sampled chunk at fixed seeds, and a prompt longer than
        the prefill chunk (its continuation chunks gather the row's pages
        from the stack). Speculative decode has its own such test."""
        cfg, params = flash_lm
        kw = dict(max_batch=2, fetch_chunk=4)
        prompts, budgets, call = PROMPTS, BUDGETS, {}
        if path == "early_exit":
            # weights under which greedy tokens vary: the fixture's model
            # repeats each prompt's last token, so no EOS could fall
            # mid-chunk
            params = dict(params, layers=jax.tree_util.tree_map(
                lambda a: 4 * a, params["layers"]), embed=dict(
                params["embed"], table=params["embed"]["table"] / 100))
            row = ServeEngine(cfg, params, eos_id=cfg.vocab_size,
                              paged=False, **kw).serve(
                PROMPTS[:1], max_new_tokens=[16])[0]
            # a first occurrence that is not a chunk's last step
            k = next(k for k in range(2, len(row))
                     if row[k] not in row[:k] and k % 4)
            kw["eos_id"] = row[k]
            prompts, budgets = PROMPTS[:1], [16]
        elif path == "sampled":
            call["sampling"] = [SamplingParams(temperature=0.8, seed=7 + i)
                                for i in range(len(PROMPTS))]
        else:
            prompts, budgets = [list(range(3, 40)), PROMPTS[1]], [5, 4]
            call["prefill_chunk"] = 8
        out_p = ServeEngine(cfg, params, **kw).serve(
            prompts, max_new_tokens=budgets, **call)
        out_c = ServeEngine(cfg, params, paged=False, **kw).serve(
            prompts, max_new_tokens=budgets, **call)
        assert out_p == out_c
        if path == "early_exit":
            assert out_p == [row[:k + 1]]

    def test_page_recycling_under_pool_pressure(self, flash_lm,
                                                paged_outputs):
        """A pool too small to hold every admitted request forces deferred
        admissions and page recycling; emitted tokens must not change
        (recycled pages carry no ghost state — the admission scatter
        overwrites every logical page)."""
        cfg, params = flash_lm
        _, out_ref = paged_outputs
        eng = ServeEngine(cfg, params, max_batch=4, fetch_chunk=3,
                          kv_pool_pages=4)     # 3 usable pages
        outs = eng.serve(PROMPTS, max_new_tokens=BUDGETS)
        assert outs == out_ref
        assert eng.serve_stats["deferred_admissions"] > 0
        assert eng.serve_stats["peak_active"] <= 2

    def test_occupancy_beats_contiguous_slots(self, flash_lm):
        """Mixed short/long workload: smax (and so the contiguous per-slot
        reserve) is driven by the longest budget, while short requests use
        a fraction of it in pages. A pool holding the HBM of 2 contiguous
        slots must admit MORE than 2 concurrent rows — the occupancy win
        the benchmark quantifies — with bit-identical tokens."""
        cfg, params = flash_lm
        budgets = [20, 3, 3, 3, 3]
        # smax buckets to 32 → 4 pages/slot; 2 contiguous slots = 8 pages.
        # long request: ceil((8+20)/8) = 4 pages; short: ceil((8+3)/8) = 2.
        eng = ServeEngine(cfg, params, max_batch=8, fetch_chunk=3,
                          kv_pool_pages=9)
        outs = eng.serve(PROMPTS, max_new_tokens=budgets)
        eng_c = ServeEngine(cfg, params, max_batch=2, fetch_chunk=3,
                            paged=False)
        assert outs == eng_c.serve(PROMPTS, max_new_tokens=budgets)
        assert eng.serve_stats["peak_active"] > 2

    def test_page_not_dividing_bucket_stays_bit_identical(self, flash_lm):
        """A page size that does not divide the power-of-two smax bucket:
        serve() must page-align smax for BOTH schedulers, or the
        contiguous engine silently drops to the XLA softmax decode while
        the paged engine runs the kernel (latent bit-identity break)."""
        cfg, params = flash_lm
        cfg12 = cfg.replace(kv_page_size=12)         # 12 ∤ 16-slot bucket
        prompts, budgets = PROMPTS[:3], BUDGETS[:3]
        out_p = ServeEngine(cfg12, params, max_batch=2, fetch_chunk=3
                            ).serve(prompts, max_new_tokens=budgets)
        out_c = ServeEngine(cfg12, params, max_batch=2, fetch_chunk=3,
                            paged=False).serve(prompts,
                                               max_new_tokens=budgets)
        assert out_p == out_c

    def test_sub_sublane_page_rejected(self, flash_lm):
        """Pages below 8 slots put the two schedulers on different
        numeric paths (the contiguous gate rejects them) — refuse
        up front."""
        cfg, params = flash_lm
        eng = ServeEngine(cfg.replace(kv_page_size=4), params, max_batch=2)
        with pytest.raises(ValueError, match="minimum page"):
            eng.serve([[5, 17, 3]], max_new_tokens=2)

    def test_pinned_oracle_falls_back_to_contiguous(self, flash_lm):
        """--attn-backend naive + --kv-page-size is honored, not silently
        overridden: the paged branch would decode through the flash kernel
        unconditionally, so serve() must fall back to the contiguous
        scheduler (which respects the oracle) with a warning."""
        cfg, params = flash_lm
        cfgn = cfg.replace(attn_impl="naive")        # kv_page_size still 8
        eng = ServeEngine(cfgn, params, max_batch=2)
        with pytest.warns(UserWarning, match="contiguous"):
            out = eng.serve([[5, 17, 3]], max_new_tokens=3)
        ref = ServeEngine(cfgn, params, max_batch=2, paged=False).serve(
            [[5, 17, 3]], max_new_tokens=3)
        assert out == ref

    def test_oversized_page_rejected(self, flash_lm):
        """kv_page_size is user config: a page whose KV tile cannot fit
        the decode kernel's VMEM budget must be refused at pool
        construction, not fail in the lowering mid-serving."""
        cfg, params = flash_lm
        big = cfg.replace(kv_page_size=1 << 20)
        eng = ServeEngine(big, params, max_batch=2)
        with pytest.raises(ValueError, match="VMEM"):
            eng.serve([[5, 17, 3]], max_new_tokens=2)

    def test_pool_too_small_raises(self, flash_lm):
        cfg, params = flash_lm
        eng = ServeEngine(cfg, params, max_batch=2, kv_pool_pages=2)
        with pytest.raises(RuntimeError, match="pages"):
            eng.serve([[5, 17, 3]], max_new_tokens=30)

    def test_paged_decode_step_cache_contract(self, flash_lm):
        """transformer.decode_step's paged branch: advances length, keeps
        table/start, scatters the new token into the owning page only."""
        cfg, params = flash_lm
        page, n_log = 8, 2
        cache = init_paged_cache(cfg, 2, 5, page, n_log)
        cache["block_table"] = jnp.asarray([[1, 3], [2, 4]], jnp.int32)
        cache["length"] = jnp.asarray([2, 9], jnp.int32)
        before_k = np.asarray(cache["k_pages"])
        h, c2 = registry.decode_step(params, cfg, jnp.asarray([7, 8]), cache)
        assert h.shape[:2] == (2, 1)
        np.testing.assert_array_equal(np.asarray(c2["length"]),
                                      np.asarray([3, 10]))
        np.testing.assert_array_equal(np.asarray(c2["block_table"]),
                                      np.asarray(cache["block_table"]))
        after_k = np.asarray(c2["k_pages"])
        changed = np.where(np.any(after_k != before_k, axis=(0, 3, 4)))
        # row 0 writes slot 2 of phys page 1; row 1 slot 1 of phys page 4
        assert set(zip(changed[0].tolist(), changed[1].tolist())) == {
            (1, 2), (4, 1)}
