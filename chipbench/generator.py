"""The one traffic generator: reads a mix file and makes its requests.

A mix fixes the (prompt length, output length) pairs of a call, in
order: ``requests_per_call`` evenly spaced quantiles of each clipped
lognormal (median, sigma, min, max), paired and ordered by a permutation
drawn from the mix's own ``pairing_seed``. So every call of every run
does the same work in the same order; ``--seed`` draws the tokens (and
the weights). The order is fixed because with chunked prefill it decides
which requests prefill first, and a seed that reordered them would change
the time to first token by a third. All requests of a call are due at its
start (offline batches).
"""
from __future__ import annotations

import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", name + ".json")) as f:
        return json.load(f)


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of the clipped lognormal ``spec``."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * np.asarray(z))
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def call_shapes(mix: dict) -> list[tuple[int, int]]:
    """The (prompt length, output length) pairs of every call, paired and
    ordered by the mix's ``pairing_seed``, never by the run's seed."""
    n = mix["requests_per_call"]
    rng = np.random.default_rng(mix["pairing_seed"])
    prompts = quantile_lengths(mix["prompt_len"], n)
    outs = quantile_lengths(mix["output_len"], n)[rng.permutation(n)]
    order = rng.permutation(n)
    return [(int(prompts[i]), int(outs[i])) for i in order]


def call_requests(mix: dict, vocab: int, seed: int, index: int):
    """Call ``index`` of a run: (prompts, output budgets), the fixed pairs
    in their fixed order, with tokens drawn from (seed, index)."""
    rng = np.random.default_rng([seed, index])
    prompts, budgets = [], []
    for p, o in call_shapes(mix):
        prompts.append(rng.integers(0, vocab, size=p).astype(np.int32)
                       .tolist())
        budgets.append(o)
    return prompts, budgets
