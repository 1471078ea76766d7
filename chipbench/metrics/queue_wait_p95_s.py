"""queue_wait_p95_s (scheduler, ``serve/engine.py`` ``_serve_loop_packed``):
95th percentile over the traced call's requests of the time from the
loop's start, when every request of the call was due, to the moment the
scheduler reserved the request's slot (``serve_stats["assign_s"]``).
The rest of a request's first-token time (``ttft_s``, on the same clock)
is its prefill once it holds a slot, so this tells whether admission or
prefill sets ``ttft_p95_s``. Read from a trace that holds the device's
ops only: a call traced without the chip (a CPU rehearsal) reads None."""
import math

import numpy as np


def _p(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def read(run):
    assign = [t for t in run.stats.get("assign_s", []) if math.isfinite(t)]
    if not assign or not run.reading.ops:
        return None
    value = _p(assign, 95)
    done = [t for t in run.stats.get("done_s", []) if math.isfinite(t)]
    print(f"queue_wait_p95_s: {len(assign)} requests assigned a slot, "
          f"p50 {_p(assign, 50)!r} s, p95 {value!r} s, last "
          f"{max(assign)!r} s; last token p50 "
          f"{_p(done, 50) if done else None!r} s, p95 "
          f"{_p(done, 95) if done else None!r} s", flush=True)
    return value
