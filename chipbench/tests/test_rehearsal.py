"""A whole run on the CPU at smoke widths, through a cell added by files
and entries only, and the check coming out false under planted faults.

Slow (interpret-mode Pallas kernels); not part of the repository's tier-1
suite. Run: ``PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest
chipbench/tests``."""
import time

from conftest import DEVICE, SEED

from chipbench import harness

def _run(cell, trace=False):
    return harness.run(cell, SEED, 1.0, trace, time.perf_counter(), DEVICE)


def test_window_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checked"]
    assert list(res)[-1] == "checked"
    assert set(res["metrics"]) == {"output_tok_s", "ttft_p50_s",
                                   "ttft_p95_s", "setup_s"}
    assert res["checked"]["compiles_in_window"]["value"] == 0
    assert res["failed"] == 0 and res["attempted"] >= 6


def test_traced_run_is_correct(cell):
    res = _run(cell, trace=True)
    assert res["correct"], res["checked"]
    # no TPU plane on the CPU: device readers find nothing and stay silent
    assert set(res["metrics"]) <= {"prefill_pad_share", "mfu"}
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_altered_token_fails(cell, monkeypatch):
    from repro.serve.engine import ServeEngine
    serve = ServeEngine.serve

    def altered(self, prompts, **kw):
        outs = serve(self, prompts, **kw)
        return [o[:1] + [(o[1] + 1) % 512] + o[2:] for o in outs]

    monkeypatch.setattr(ServeEngine, "serve", altered)
    res = _run(cell)
    assert not res["correct"]


def test_unchanged_cache_fails(cell, monkeypatch):
    """Decode steps that return the K/V pool unchanged."""
    from repro.models import registry
    step = registry.decode_step

    def stale(params, cfg, tokens, cache, **kw):
        hidden, new = step(params, cfg, tokens, cache, **kw)
        return hidden, dict(new, k_pages=cache["k_pages"],
                            v_pages=cache["v_pages"])

    monkeypatch.setattr(registry, "decode_step", stale)
    res = _run(cell)
    assert not res["correct"]
