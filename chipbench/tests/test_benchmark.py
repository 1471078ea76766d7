"""BENCHMARK.json against the benchmark's contract, and the command's
refusals without a TPU v5e."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys(bench):
    assert list(bench) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    assert bench["paths"] == ["chipbench"]
    assert bench["command"][1].startswith("chipbench/")
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_configs(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank")) and key not in (
                "d_model", "d_ff", "num_heads", "num_kv_heads", "head_dim")
        ref = os.path.join(HERE, "references", cfg["reference"] + ".py")
        assert os.path.exists(ref)


def test_workloads(bench):
    configs = {c["name"] for c in bench["configs"]}
    used, pairs = set(), set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert w["config"] in configs
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        for sub, name in (("traffic", w["traffic"]), ("limits", w["name"])):
            assert os.path.exists(os.path.join(HERE, sub, name + ".json"))
    assert used == configs


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert e2e == {"output_tok_s", "ttft_p50_s", "ttft_p95_s", "setup_s"}
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    names = set()
    for m in bench["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert os.path.exists(os.path.join(HERE, "metrics",
                                           m["name"] + ".py"))
    assert not names & e2e


def _run_command(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "olmo-1b-dbb.chat", "--seed", str(2 ** 31 + 3), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_refuses_without_a_tpu():
    r = _run_command(ROOT, {})
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not r.stdout.strip()


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    r = _run_command(tmp_path, {})
    assert r.returncode != 0
    assert not r.stdout.strip()


def test_unknown_device_kind_is_an_error():
    from chipbench import peaks
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
