"""The offline-batch client: hands ``ServeEngine.serve`` one batch of a
mix's requests per call, all due at the call's start, and records what
came back. Calls repeat until the window's seconds have passed; no call
starts after that, and the window ends when the last call returns."""
from __future__ import annotations

import dataclasses
import time
from typing import List, Tuple

from chipbench import generator


@dataclasses.dataclass
class Call:
    prompts: List[List[int]]
    budgets: List[int]
    outputs: List[List[int]]
    # per request, from the start of the engine's loop, a few ms after the
    # call's start (``serve_stats["ttft_s"]``)
    ttft_s: List[float]
    start: float
    end: float
    stats: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def finished(self) -> List[bool]:
        """A request finished when it returned its whole budget."""
        return [len(o) == b for o, b in zip(self.outputs, self.budgets)]


def serve(engine, mix: dict, prompts, budgets) -> Call:
    """One call: every request due now; returns when all are done."""
    eng = mix["engine"]
    start = time.perf_counter()
    outputs = engine.serve(prompts, max_new_tokens=budgets,
                           prompt_bucket=eng["prompt_bucket"],
                           prefill_chunk=eng["prefill_chunk"],
                           fetch_chunk=eng["fetch_chunk"])
    end = time.perf_counter()
    stats = dict(engine.serve_stats)
    ttft = list(stats.pop("ttft_s", [float("nan")] * len(prompts)))
    return Call(prompts, list(budgets), outputs, ttft, start, end, stats)


def run_window(engine, mix: dict, vocab: int, seed: int,
               seconds: float) -> List[Call]:
    calls: List[Call] = []
    t0 = time.perf_counter()
    index = 0
    while time.perf_counter() - t0 < seconds:
        prompts, budgets = generator.call_requests(mix, vocab, seed, index)
        calls.append(serve(engine, mix, prompts, budgets))
        index += 1
    return calls


def warm_scenarios(mix: dict) -> List[Tuple[List[int], int]]:
    """Serve calls that compile every program the mix's calls can reach.

    With ``prefill_chunk`` 0 every prompt of a call prefills in one packed
    call, so one scenario, the call's own prompt lengths, is enough.
    Otherwise ``_serve_loop_packed`` packs first chunks into one call of at most
    ``prefill_chunk`` tokens, bucketed to a power of two (at least 8), and
    gathers one row per request, bucketed to a power of two; a prompt
    longer than the chunk continues in chunks whose length buckets the same
    way. One scenario per reachable (token bucket, row bucket) of the
    packed call, and one whose prompts end in every continuation bucket.
    Each scenario is (prompt lengths, output budget); prompts stay within
    the mix's range, so the cache length is that of the window's calls."""
    chunk = mix["engine"]["prefill_chunk"]
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    slots = mix["engine"]["max_batch"]
    if chunk <= 0:
        # whole prompts: with every request of a call in a slot at once,
        # the one packed call prefills the call's fixed set of prompts
        if mix["requests_per_call"] > slots:
            raise ValueError("whole-prompt prefill needs every request of "
                             "a call in a slot at once")
        return [([p for p, _ in generator.call_shapes(mix)], 2)]
    cmin, cmax = min(lo, chunk), min(hi, chunk)

    def buckets(top: int, first: int):
        b, out = first, []
        while True:
            out.append(b)
            if b >= top:
                return out
            b *= 2

    scenarios = []
    for tp in buckets(_bucket(chunk, 8), 8):
        for gp in buckets(slots, 1):
            # g requests whose first chunks total more than half of tp
            g = min(gp, tp // cmin, slots)
            if g < 1 or _bucket(g, 1) != gp:
                continue
            total = min(tp, chunk, g * cmax)
            if total < g * cmin or _bucket(total, 8) != tp:
                continue
            lens = [total // g + (1 if i < total % g else 0)
                    for i in range(g)]
            scenarios.append((lens, 2))
    tails = [c for c in buckets(_bucket(chunk, 8), 8)
             if lo <= chunk + min(c, chunk) <= hi]
    if tails:
        scenarios.append(([chunk + min(c, chunk) for c in tails], 2))
    return scenarios


def _bucket(n: int, minimum: int) -> int:
    b = max(minimum, 1)
    while b < n:
        b *= 2
    return b
