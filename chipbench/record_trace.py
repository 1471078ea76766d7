#!/usr/bin/env python3
"""Records a trace for ``chipbench/tests/data`` (not run by the benchmark).

    python3 chipbench/record_trace.py --workload <cell> --seed <n> \\
        --out chipbench/tests/data/<cell>.json.gz

After the cell's set-up, a small call (the first four requests of the
cell's mix, eight tokens each at most) is served once to compile its
shapes, then again under the profiler; the reading is written with the
per-layer numbers and the breakdown the benchmark reads from it, which
``tests/test_trace.py`` reads again. Needs the cell's chip, like
``run.py``.
"""
import argparse
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from chipbench import client, generator, harness, trace as tr
    cell = harness.load_cell(args.workload)
    cell.device_kind = harness.device_summary(cell.chips)["kind"]
    sys.path.insert(0, os.path.join(ROOT, "src"))
    engine, _, _ = harness.set_up(cell, args.seed, time.perf_counter())
    prompts, budgets = generator.call_requests(
        cell.mix, cell.cfg["vocab_size"], args.seed, 0)
    few = (prompts[:4], [min(b, 8) for b in budgets[:4]])
    client.serve(engine, cell.mix, *few)
    tdir = tempfile.mkdtemp(prefix="chipbench_trace_")
    try:
        call, reading = harness.traced(engine, cell.mix, *few, tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    metrics, breakdown = tr.per_layer(cell, reading, call, call.stats)
    tr.dump(args.out, cell, reading,
            tr.make_run(cell, reading, [len(p) for p in call.prompts],
                        [len(o) for o in call.outputs], call.stats),
            metrics, breakdown)
    print(f"wrote {args.out}: {metrics}", flush=True)
    return 0


if __name__ == "__main__":
    # the compile cache of chipbench/run.py, set before JAX is imported
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path.insert(0, ROOT)
    raise SystemExit(main())
