"""A tiny cell for the CPU tests: smoke widths, added to a copy of the
benchmark by files and entries only."""
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness, peaks  # noqa: E402

CELL = "tiny-dbb.tiny"
SEED = 2 ** 33 + 5
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}

TINY = dict(name="tiny-dbb", num_layers=2, d_model=128, num_heads=4,
            num_kv_heads=2, head_dim=32, d_ff=256, vocab_size=512,
            sliding_window=48, kv_page_size=8)
MIX = {"requests_per_call": 8,
       "prompt_len": {"median": 20, "sigma": 0.8, "min": 8, "max": 40},
       "output_len": {"median": 10, "sigma": 0.8, "min": 6, "max": 16},
       "pairing_seed": 0,
       "engine": {"max_batch": 4, "prefill_chunk": 16, "prompt_bucket": 64,
                  "fetch_chunk": 4, "kv_pool_pages": 0}}
# tiny-size limits, set like the cells' (PERF.md) from readings at this
# size: sound runs read 0-0.0013 (max gap) and 0-3.3e-5 (mean gap), the
# planted faults 0.2 and 0.015 or more
LIMITS = {"max_logit_gap": 0.008, "mean_logit_gap": 0.0002}
# the control's tiny cell: one call of more served tokens, so that the
# mean gap separates the INT8 path; over seeds 11-13 and 2-4 sound runs
# read 4.7e-6-1.6e-5 (mean gap) and 6.8e-4-2.3e-3 (max gap), the INT8
# path 3.1e-5-7.9e-5 and 3.5e-3-7.4e-3
CONTROL_CELL = "tiny-dbb.tiny-control"
CONTROL_MIX = dict(MIX, requests_per_call=16, output_len={
    "median": 32, "sigma": 0.8, "min": 16, "max": 64})
CONTROL_LIMITS = {"max_logit_gap": 0.008, "mean_logit_gap": 2.2e-5}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """A copy of the benchmark with two more cells, made by adding files
    (configuration, mixes, limits) and entries (the workloads, the
    metrics' cell lists) only."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(HERE, "configs",
                           "starcoder2-15b-l8-dbb.json")) as f:
        cfg = dict(json.load(f), **TINY)
    for sub, name, obj in (("configs", "tiny-dbb", cfg),
                           ("traffic", "tiny", MIX),
                           ("limits", CELL, LIMITS),
                           ("traffic", "tiny-control", CONTROL_MIX),
                           ("limits", CONTROL_CELL, CONTROL_LIMITS)):
        path = root / "chipbench" / sub / (name + ".json")
        assert not path.exists()
        path.write_text(json.dumps(obj))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    for name, mix in ((CELL, "tiny"), (CONTROL_CELL, "tiny-control")):
        b["workloads"].append({"name": name, "config": "tiny-dbb",
                               "traffic": mix, "chips": 1, "why": "test"})
        for m in b["per_layer"]:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


def _load(bench, monkeypatch, name):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5e"])
    return harness.load_cell(name, bench_path=str(bench / "BENCHMARK.json"),
                             root=str(bench / "chipbench"))


@pytest.fixture
def cell(bench, monkeypatch):
    return _load(bench, monkeypatch, CELL)


@pytest.fixture
def control_cell(bench, monkeypatch):
    return _load(bench, monkeypatch, CONTROL_CELL)


