#!/usr/bin/env python3
"""Entry point of the on-chip benchmark; see ``chipbench/harness.py``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# JAX's persistent compilation cache lives at a fixed path in the checkout,
# and every program goes into it, however fast it compiled
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(harness.main(t_start=T_START))
