"""The generator: every seed gets the same work, in its own order."""
import numpy as np
import pytest

from chipbench import generator

MIXES = ["chat"]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    m = generator.load_mix(mix)
    a = generator.call_requests(m, 1000, 2 ** 31 + 17, 3)
    b = generator.call_requests(m, 1000, 2 ** 31 + 17, 3)
    assert a == b


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_and_calls_share_the_work(mix):
    m = generator.load_mix(mix)
    shapes = generator.call_shapes(m)
    seen = []
    for seed, index in [(0, 0), (1, 0), (2 ** 33 + 1, 5)]:
        prompts, budgets = generator.call_requests(m, 1000, seed, index)
        assert [(len(p), b) for p, b in zip(prompts, budgets)] == shapes
        assert all(0 <= t < 1000 for p in prompts for t in p)
        seen.append(prompts)
    assert seen[0] != seen[1] and seen[0] != seen[2]


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_within_the_clip(mix):
    m = generator.load_mix(mix)
    for key in ("prompt_len", "output_len"):
        spec = m[key]
        x = generator.quantile_lengths(spec, m["requests_per_call"])
        assert x.min() >= spec["min"] and x.max() <= spec["max"]
        assert np.all(np.diff(x) >= 0)
        # the median of the quantiles is the mix's median
        assert abs(np.median(x) - spec["median"]) <= 0.05 * spec["median"]
