"""One run of one cell: set up, measure, check, print one JSON line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``configs/<config>.json``: the model as it is run, its source and cut;
* ``references/<reference>.py``: the plain reference the config names;
* ``traffic/<traffic>.json``: the mix, read by ``generator.py``;
* ``metrics/<metric>.py``: the reader of one per-layer metric;
* ``limits/<cell>.json``: the limit of each number the check compares.

``--trace 0`` measures the end-to-end metrics over a window of calls;
``--trace 1`` profiles one whole call after the warm-up instead and reads
the per-layer metrics from it. Both check what the timed path served.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import warnings

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
V5E_KINDS = ("TPU v5 lite", "TPU v5e")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*parts) -> None:
    print(*parts, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with everything its name finds."""
    name: str
    chips: int
    cfg: dict
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: str = HERE
    device_kind: str = ""

    def reference(self):
        return load_module(os.path.join(
            self.root, "references", self.cfg["reference"] + ".py"),
            "chipbench_reference_" + self.cfg["reference"])

    def metric_reader(self, name: str):
        return load_module(os.path.join(self.root, "metrics", name + ".py"),
                           "chipbench_metric_" + name.replace(".", "_"))


def load_cell(name: str, bench_path: str = os.path.join(ROOT,
                                                         "BENCHMARK.json"),
              root: str = HERE) -> Cell:
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(
        name=name, chips=w["chips"],
        cfg=load_json(root, "configs", w["config"] + ".json"),
        mix=load_json(root, "traffic", w["traffic"] + ".json"),
        limits=load_json(root, "limits", name + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)], root=root)


def device_summary(chips: int) -> dict:
    """The device as JAX reports it; refuses anything but a TPU v5e."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0].platform is "
                         f"{d.platform!r}")
    if d.device_kind not in V5E_KINDS:
        raise SystemExit(f"device_kind {d.device_kind!r} is not a TPU v5e")
    if len(devs) < chips:
        raise SystemExit(f"needs {chips} chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.config import DbbConfig, ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg.items() if k in fields and k != "dbb"}
    return ModelConfig(param_dtype=cfg["dtype"],
                       dbb=DbbConfig(enabled=True, **cfg["dbb"]), **kw)


class CompileCounter:
    """Counts the backend compilations (or compile-cache loads) JAX makes
    while it is on."""

    def __init__(self):
        from jax import monitoring
        self.on, self.names = False, []
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, secs, **kw):
        if self.on and event == COMPILE_EVENT:
            self.names.append(kw.get("fun_name", "?"))


def _percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def set_up(cell: Cell, seed: int, t_start: float, int8: bool = False):
    """Weights from the seed, packed by the program, the engine built and
    every shape of the mix warmed up. Returns (engine, routes, setup_s).
    ``int8`` packs INT8 values with per-channel scales instead: the
    program's own lower-precision path, the check's control."""
    import jax

    from chipbench import client, weights
    from repro.core.dbb_linear import pack_tree
    from repro.kernels import dispatch
    from repro.serve.engine import ServeEngine

    mc = model_config(cell.cfg)
    dm = weights.dims(cell.cfg)
    eng_cfg = cell.mix["engine"]
    marks = [("start", time.perf_counter())]
    with warnings.catch_warnings(record=True) as caught, \
            dispatch.record_routes() as routes:
        warnings.simplefilter("always")
        params = weights.program_params(
            seed, dm, lambda tree: pack_tree(tree, mc.dbb, quantize=int8))
        jax.block_until_ready(params)
        marks.append(("weights", time.perf_counter()))
        engine = ServeEngine(mc, params, max_batch=eng_cfg["max_batch"],
                             eos_id=dm.vocab,   # never emitted
                             fetch_chunk=eng_cfg["fetch_chunk"],
                             prefill_chunk=eng_cfg["prefill_chunk"],
                             kv_pool_pages=eng_cfg["kv_pool_pages"])
        del params              # the engine holds the one resident copy
        jax.block_until_ready(engine.params)
        marks.append(("engine", time.perf_counter()))
        log(f"HBM after the engine's construction: {in_use_bytes()} bytes "
            f"in use, peak {peak_bytes()}")
        rng = np.random.default_rng([seed, 7])
        for lens, budget in client.warm_scenarios(cell.mix):
            prompts = [rng.integers(0, dm.vocab, n).tolist() for n in lens]
            client.serve(engine, cell.mix, prompts, [budget] * len(lens))
        marks.append(("warm-up", time.perf_counter()))
    for w in caught:
        log(f"warning during set-up: {w.category.__name__}: {w.message}")
    log("set-up steps: " + ", ".join(
        f"{name} {t - marks[i][1]:.3f} s"
        for i, (name, t) in enumerate(marks[1:])))
    return engine, sorted(f"{d}:{r}" for d, r in routes), \
        time.perf_counter() - t_start


def _memory(*keys: str) -> int:
    import jax
    return max(sum(int((d.memory_stats() or {}).get(k, 0)) for k in keys)
               for d in jax.local_devices())


def peak_bytes() -> int:
    """The process's peak on the fullest chip: the allocator's peak and
    the peak reserved for the compiled programs' temporaries (on TPU the
    serving programs' copy of the K/V pool), which the allocator's own
    count leaves out."""
    return _memory("peak_bytes_in_use", "peak_bytes_reserved")


def in_use_bytes() -> int:
    return _memory("bytes_in_use")


def traced(engine, mix, prompts, budgets, directory):
    """One call under the profiler: (the call, its trace's reading)."""
    import jax

    from chipbench import client, trace as tr
    with jax.profiler.trace(directory):
        with jax.profiler.TraceAnnotation(tr.CALL_SPAN):
            call = client.serve(engine, mix, prompts, budgets)
    return call, tr.read_dir(directory)


def end_to_end(calls, setup_s: float) -> tuple:
    tokens = sum(len(o) for c in calls for o in c.outputs)
    window = calls[-1].end - calls[0].start
    ttft = []
    for c in calls:
        for ok, t in zip(c.finished(), c.ttft_s):
            ttft.append(t if ok and math.isfinite(t) else math.inf)
    values = {"output_tok_s": tokens / window,
              "ttft_p50_s": _percentile(ttft, 50),
              "ttft_p95_s": _percentile(ttft, 95),
              "setup_s": setup_s}
    beyond = sum(1 for t in ttft if t > values["ttft_p95_s"])
    return values, {"requests": len(ttft), "beyond_p95": beyond,
                    "tokens": tokens, "window_s": window}


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, device: dict, int8: bool = False) -> dict:
    """One run; ``int8`` serves through the program's INT8 path instead,
    the check's control (``control.py``)."""
    import jax

    from chipbench import client, generator, trace as tr

    cell.device_kind = device["kind"]
    counter = CompileCounter()
    engine, routes, setup_s = set_up(cell, seed, t_start, int8=int8)
    log(f"device: {device}")
    log(f"routes during set-up: {routes}")
    log(f"set-up: {setup_s:.3f} s")
    vocab = cell.cfg["vocab_size"]
    counter.on = True
    reading = None
    if trace:
        prompts, budgets = generator.call_requests(cell.mix, vocab, seed, 0)
        tdir = tempfile.mkdtemp(prefix="chipbench_trace_")
        try:
            call, reading = traced(engine, cell.mix, prompts, budgets,
                                    tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        calls = [call]
    else:
        calls = client.run_window(engine, cell.mix, vocab, seed, seconds)
    counter.on = False
    peak, in_use = peak_bytes(), in_use_bytes()
    log(f"compiles inside the window: {len(counter.names)} "
        f"{sorted(set(counter.names))}")
    n_req = sum(len(c.prompts) for c in calls)
    n_ok = sum(sum(c.finished()) for c in calls)
    log(f"calls: {len(calls)} of {[round(c.seconds, 3) for c in calls]} s; "
        f"requests sent {n_req}, succeeded {n_ok}, failed {n_req - n_ok}")
    log(f"HBM after the window: {in_use} bytes in use, peak {peak}; "
        f"{jax.local_devices()[0].memory_stats()}")
    device = dict(device, memory_peak_bytes=peak)
    stats = calls[0].stats if trace else None
    del engine
    gc.collect()

    result = {"correct": False, "attempted": n_req, "failed": n_req - n_ok}
    if trace:
        metrics, extra = tr.per_layer(cell, reading, calls[0], stats)
        device.update(busy_s=reading.busy_s, window_s=reading.window_s)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = extra
    else:
        values, counts = end_to_end(calls, setup_s)
        log(f"TTFT over {counts['requests']} requests; "
            f"{counts['beyond_p95']} beyond p95; {counts['tokens']} tokens "
            f"in {counts['window_s']:.6f} s")
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in values.items() if k in units}
        result["device"] = device
    from chipbench import check
    compared = check.compare(cell, seed, calls)
    compared["compiles_in_window"] = {"value": len(counter.names),
                                      "limit": 0}
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in compared.values())
    result["checked"] = compared
    for k, c in compared.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return result


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    device = device_summary(cell.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    result = run(cell, args.seed, args.seconds, bool(args.trace), t_start,
                 device)
    print(json.dumps(result), flush=True)
    return 0
