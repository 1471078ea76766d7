#!/usr/bin/env python3
"""Readings the check's limits are set from (not run by the benchmark).

    python3 chipbench/control.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 [--int8-seeds 4,5,6]

Each seed is one whole run of ``harness.run`` in this process, with the
benchmark's own set-up, window and comparison: ``--seeds`` as the
program serves the cell, ``--int8-seeds`` with the program's own
lower-precision path on (INT8 DBB values with per-channel scales), the
check's control, which has to come out not ``correct``. One JSON line per
run: its kind, seed, ``correct``, every number compared with its limit,
and the reference's counts. Needs the cell's chip, like ``run.py``.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reading(cell, seed: int, seconds: float, device: dict,
            int8: bool = False) -> dict:
    from chipbench import harness
    res = harness.run(cell, seed, seconds, False, time.perf_counter(),
                      device, int8=int8)
    return {"kind": "int8" if int8 else "program", "seed": seed,
            "correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"],
            "checked": res["checked"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--int8-seeds", default="")
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    device = harness.device_summary(cell.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    runs = [(int(s), False) for s in args.seeds.split(",") if s] + \
        [(int(s), True) for s in args.int8_seeds.split(",") if s]
    for seed, int8 in runs:
        print(json.dumps(reading(cell, seed, args.seconds, device, int8)),
              flush=True)
    return 0


if __name__ == "__main__":
    # the compile cache of chipbench/run.py, set before JAX is imported
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path.insert(0, ROOT)
    raise SystemExit(main())
