"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, every
request the window finished is run through the plain reference: each
prompt followed by its served tokens. A served token's gap is how far
its logit lies below the reference's best logit at its position (0 when
it is the reference's own greedy pick); the numbers compared are the
widest gap and the mean gap. Requests that did not return their whole
budget are compared too, against the limit 0.

The limits come from ``limits/<cell>.json``; how each was set is in
PERF.md.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import weights

def finished(calls):
    """(prompt, served) of every request of the calls that returned its
    whole budget."""
    return [(p, o) for c in calls
            for p, o, ok in zip(c.prompts, c.outputs, c.finished()) if ok]


def reference_gaps(cell, seed: int, requests):
    """Per-request arrays of the reference's gaps (see the reference's
    ``served_gaps``), weights drawn again from the seed."""
    dm = weights.dims(cell.cfg)
    ref = cell.reference()
    return ref.served_gaps(
        cell.cfg,
        lambda layer: weights.layer_tensors(weights.layer_key(seed, layer),
                                            dm),
        lambda: weights.top_tensors(weights.top_key(seed), dm),
        requests)


def numbers(gaps) -> dict:
    """The numbers a check can compare, from the per-request gaps."""
    flat = np.concatenate([np.asarray(g, np.float64) for g in gaps]) \
        if gaps else np.zeros((0,))
    if flat.size == 0:
        return {"max_logit_gap": float("inf"),
                "mean_logit_gap": float("inf")}
    return {"max_logit_gap": float(flat.max()),
            "mean_logit_gap": float(flat.mean())}


def readings(cell, seed: int, calls) -> dict:
    """Every number of the comparison, compared or not."""
    picked = finished(calls)
    t0 = time.perf_counter()
    out = numbers(reference_gaps(cell, seed, picked))
    out.update(requests=len(picked),
               served_tokens=sum(len(o) for _, o in picked),
               prompt_tokens=sum(len(p) for p, _ in picked),
               reference_s=time.perf_counter() - t0)
    return out


def compare(cell, seed: int, calls) -> dict:
    """Each number that ``limits/<cell>.json`` holds a limit for, beside
    that limit, and the count of requests that came back short."""
    failed = sum(len(c.prompts) - sum(c.finished()) for c in calls)
    got = readings(cell, seed, calls)
    print(f"check: reference over {got['requests']} requests, "
          f"{got['served_tokens']} served tokens, {got['prompt_tokens']} "
          f"prompt tokens, in {got['reference_s']:.3f} s; "
          f"max gap {got['max_logit_gap']!r}, mean gap "
          f"{got['mean_logit_gap']!r}", flush=True)
    out = {"failed_requests": {"value": failed, "limit": 0}}
    for name, limit in cell.limits.items():
        out[name] = {"value": got[name], "limit": limit}
    return out
