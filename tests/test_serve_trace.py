"""Spans and counters of the serving loop (`ServeEngine.serve`).

The scheduler marks its phases with profiler host spans (``serve.call``,
``serve.iter``, ``serve.host.*``, ``serve.sync.*``), counts decode-batch
occupancy in ``serve_stats`` (``decode_steps``, ``decode_row_steps``,
``decode_surplus_row_steps``) and keeps a per-request timeline
(``assign_s``, ``ttft_s``, ``done_s``). On the CPU tiny engine of
``test_packed_prefill.py``: the spans nest and match the counters, the
counters add up to the tokens served, the timeline is ordered, and the
profiler changes no output.
"""
import functools
import glob
import math
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import registry
from repro.serve.engine import ServeEngine
from repro.serve.sampling import SamplingParams

FETCH = 4
HOST_SPANS = {"serve.host.assign", "serve.host.pack",
              "serve.host.dispatch_prefill", "serve.host.dispatch_continue",
              "serve.host.install", "serve.host.dispatch_decode",
              "serve.host.retire"}
SYNC_SPANS = {"serve.sync.first_token", "serve.sync.decode"}


@functools.lru_cache(maxsize=None)
def _engine(paged: bool) -> ServeEngine:
    cfg = get_config("olmo-1b", smoke=True).replace(remat="none")
    if paged:
        cfg = cfg.replace(attn_impl="flash", kv_page_size=8)
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    return ServeEngine(cfg, params, max_batch=2, fetch_chunk=FETCH)


def _requests(vocab: int):
    """Five requests for two slots, so most wait for one; prompts longer
    than the prefill chunk of 8 continue in chunks, and the budgets end
    rows in the middle of a decode chunk."""
    rng = np.random.default_rng(5)
    lens, budgets = (3, 11, 6, 17, 1), [5, 2, 7, 1, 6]
    prompts = [list(map(int, rng.integers(1, vocab - 1, size=n)))
               for n in lens]
    return prompts, budgets


def _serve(paged: bool, mode: str = "packed"):
    eng = _engine(paged)
    prompts, budgets = _requests(eng.cfg.vocab_size)
    outs = eng.serve(prompts, budgets, prefill_mode=mode, prefill_chunk=8)
    return eng, prompts, budgets, outs, dict(eng.serve_stats)


@functools.lru_cache(maxsize=None)
def _traced(paged: bool, mode: str, directory: str):
    with jax.profiler.trace(directory):
        eng, prompts, budgets, outs, stats = _serve(paged, mode)
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    data = jax.profiler.ProfileData.from_file(files[0])
    spans = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("serve.")]
    return spans, outs, stats, budgets


@pytest.fixture(scope="module")
def trace_root(tmp_path_factory):
    return tmp_path_factory.mktemp("serve_trace")


def _inside(span, outer) -> bool:
    return outer[1] <= span[1] and span[2] <= outer[2]


CASES = [(False, "packed"), (True, "packed"), (False, "padded")]


@pytest.mark.parametrize("paged,mode", CASES)
def test_spans_nest_in_iterations_of_one_call(paged, mode, trace_root):
    spans, *_ = _traced(paged, mode, str(trace_root / f"{paged}-{mode}"))
    calls = [s for s in spans if s[0] == "serve.call"]
    iters = [s for s in spans if s[0] == "serve.iter"]
    leaves = [s for s in spans if s[0] in HOST_SPANS | SYNC_SPANS]
    assert len(calls) == 1 and iters and leaves
    assert {s[0] for s in spans} <= HOST_SPANS | SYNC_SPANS | {
        "serve.call", "serve.iter"}
    assert all(_inside(s, calls[0]) for s in iters)
    for s in leaves:
        assert any(_inside(s, i) for i in iters), s
    if mode == "packed":
        assert {s[0] for s in leaves} == HOST_SPANS | SYNC_SPANS


@pytest.mark.parametrize("paged,mode", CASES)
def test_one_decode_dispatch_span_per_chunk(paged, mode, trace_root):
    spans, _, stats, _ = _traced(paged, mode,
                                 str(trace_root / f"{paged}-{mode}"))
    n = sum(1 for s in spans if s[0] == "serve.host.dispatch_decode")
    assert n > 0 and n * FETCH == stats["decode_steps"]
    assert n == sum(1 for s in spans if s[0] == "serve.sync.decode")


@pytest.mark.parametrize("paged,mode", CASES)
def test_outputs_same_with_the_profiler_on(paged, mode, trace_root):
    _, traced_outs, traced_stats, budgets = _traced(
        paged, mode, str(trace_root / f"{paged}-{mode}"))
    _, _, _, outs, stats = _serve(paged, mode)
    assert traced_outs == outs
    assert [len(o) for o in outs] == budgets
    for key in ("decode_steps", "decode_row_steps",
                "decode_surplus_row_steps"):
        assert traced_stats[key] == stats[key], key


@pytest.mark.parametrize("paged,mode", CASES)
def test_row_steps_are_the_tokens_decode_served(paged, mode):
    eng, prompts, budgets, outs, stats = _serve(paged, mode)
    # greedy: one token per live row-step; every request with a budget
    # took its first token from prefill
    from_prefill = sum(1 for b in budgets if b > 0)
    assert stats["decode_row_steps"] == sum(map(len, outs)) - from_prefill
    assert stats["decode_surplus_row_steps"] > 0
    assert (stats["decode_row_steps"] + stats["decode_surplus_row_steps"]
            <= eng.max_batch * stats["decode_steps"])


@pytest.mark.parametrize("paged", [False, True])
def test_row_steps_under_speculative_decoding(paged):
    """A speculative row-step emits 1..k+1 tokens: the row-steps count the
    steps in which a live row consumed at least one; with the surplus
    they are every step of every row that decoded (``spec_steps``)."""
    eng = _engine(paged)
    prompts, budgets = _requests(eng.cfg.vocab_size)
    sp = [SamplingParams(temperature=0.7, seed=40 + i)
          for i in range(len(prompts))]
    outs = eng.serve(prompts, budgets, sampling=sp, draft_k=2)
    st = eng.serve_stats
    decoded = sum(map(len, outs)) - len(prompts)
    assert 0 < st["decode_row_steps"] <= decoded
    assert decoded <= 3 * st["decode_row_steps"]
    assert (st["decode_row_steps"] + st["decode_surplus_row_steps"]
            == st["spec_steps"])
    assert (st["spec_steps"] <= eng.max_batch * st["decode_steps"])


@pytest.mark.parametrize("paged,mode", CASES)
def test_timeline_is_ordered(paged, mode):
    _, prompts, _, _, stats = _serve(paged, mode)
    rows = list(zip(stats["assign_s"], stats["ttft_s"], stats["done_s"]))
    assert len(rows) == len(prompts)
    for a, t, d in rows:
        assert all(math.isfinite(x) for x in (a, t, d))
        assert 0 <= a <= t <= d
    # five requests for two slots: some wait for a slot to free
    assert sorted(a for a, _, _ in rows)[-1] > min(d for _, _, d in rows)
