"""Decode/serving A/B (DESIGN.md §9): tokens/sec and weight residency.

Two measurements on one DBB-packed smoke LM:

1. **Scheduling + sync**: the pre-PR serving loop (static waves padded to
   `max_batch`, one ``np.asarray`` host round-trip per decoded token)
   against the continuous-batching engine (mid-stream admission, chunked
   device-side token fetch). Same jitted decode step underneath — the A/B
   isolates the serving layer. With a mixed short/long workload the static
   wave drains to its slowest request while finished slots idle; the
   continuous scheduler backfills them.

2. **Weight residency**: HBM bytes of the stacked layer weights packed
   (values + bitmask, what the streaming decode path reads per token)
   vs dense, and the structural no-materialization assertion — tracing the
   Pallas-route decode step on packed params must hit `decompress_xla`
   ZERO times (every dense expand of a packed weight goes through it), so
   peak weight bytes per decoded token are the compressed bytes, not
   compressed + a dense transient. The XLA route is traced as a control
   (it must decompress per layer).

Emitted as the ``decode_serve`` section of ``BENCH_decode.json`` by
`benchmarks.run` (CI smoke-runs it alongside ``BENCH_conv.json``).
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

SPEEDUP_FLOOR = 1.3     # acceptance: continuous ≥ 1.3x the pre-PR loop


def _build(seed: int = 0):
    from repro.configs import get_config
    from repro.core.dbb_linear import pack_tree
    from repro.core.sparsity import apply_dbb_to_tree
    from repro.models import registry

    cfg = get_config("olmo-1b", smoke=True).replace(remat="none")
    dbb = cfg.dbb.__class__(enabled=True, block=8, nnz=4)
    cfg = cfg.replace(dbb=dbb)
    params = registry.init_params(jax.random.PRNGKey(seed), cfg)
    proj = apply_dbb_to_tree(params, dbb, straight_through=False)
    packed = pack_tree(proj, dbb)
    return cfg, proj, packed


def _workload(n_req: int, rng: np.random.Generator):
    """Mixed decode lengths: one long request per arrival wave — the
    static scheduler drains every wave to that request while the finished
    slots idle; the continuous scheduler backfills them. Prompt lengths
    are fixed so both schedulers reuse one compiled prefill/decode shape —
    the A/B measures scheduling and host syncs, not compilation."""
    prompts = [list(rng.integers(2, 500, size=6)) for _ in range(n_req)]
    budgets = [64 if i % 4 == 0 else 4 for i in range(n_req)]
    return prompts, budgets


def _static_per_token(eng, prompts: List[List[int]], budgets: List[int]
                      ) -> List[List[int]]:
    """The pre-PR serving loop: requests in arrival-order waves of
    `max_batch`, one prefill per wave, then a decode loop with ONE HOST
    SYNC PER TOKEN (`np.asarray(cur)`) and no slot backfill — finished
    rows ride along until the wave's longest request drains."""
    from repro.models import registry

    outs: List[List[int]] = []
    mb = eng.max_batch
    for w0 in range(0, len(prompts), mb):
        wave_p = prompts[w0:w0 + mb]
        wave_b = budgets[w0:w0 + mb]
        b = len(wave_p)
        max_len = max(len(p) for p in wave_p)
        total = max_len + max(wave_b)
        toks = np.zeros((mb, max_len), np.int32)
        start = np.zeros((mb,), np.int32)
        for i, p in enumerate(wave_p):
            toks[i, max_len - len(p):] = p
            start[i] = max_len - len(p)
        cache = registry.init_cache(eng.cfg, mb, total)
        batch = {"tokens": jnp.asarray(toks)}
        if start.any():
            batch["start"] = jnp.asarray(start)
        cur, cache = eng._prefill(eng.params, cache, batch)
        wave_outs: List[List[int]] = [[] for _ in range(b)]
        done = np.zeros(mb, bool)
        for _ in range(max(wave_b)):
            host = np.asarray(cur)                  # per-token host sync
            for i in range(b):
                if not done[i]:
                    wave_outs[i].append(int(host[i]))
                    done[i] |= (host[i] == eng.eos_id
                                or len(wave_outs[i]) >= wave_b[i])
            if done[:b].all():
                break
            cur, cache = eng._decode(eng.params, cache, cur)
        outs.extend(wave_outs)
    return outs


def _residency(cfg, packed, proj) -> Dict:
    """Packed vs dense stacked-layer HBM bytes + the structural assertion
    that the Pallas-route decode step never materializes a dense copy of a
    stacked layer weight."""
    from repro.core import dbb_linear
    from repro.core.dbb_linear import tree_footprint_bytes
    from repro.models import registry
    from repro.serve.engine import make_decode_step

    packed_bytes = tree_footprint_bytes(packed["layers"])
    dense_bytes = tree_footprint_bytes(proj["layers"])
    tok = jnp.asarray([7], jnp.int32)

    def trace_calls(route_cfg) -> int:
        cache = registry.init_cache(route_cfg, 1, 8)
        step = make_decode_step(route_cfg)
        before = dbb_linear.DECOMPRESS_STATS["calls"]
        jax.eval_shape(step, packed, cache, tok)    # trace, don't run
        return dbb_linear.DECOMPRESS_STATS["calls"] - before

    pallas_calls = trace_calls(cfg.replace(gemm_impl="pallas"))
    xla_calls = trace_calls(cfg.replace(gemm_impl="xla"))
    # peak-bytes assertion: on the streaming route the per-token weight
    # traffic (and residency) is the compressed bytes — a single
    # decompress_xla hit would mean a dense transient rode along
    assert pallas_calls == 0, (
        f"packed streaming decode materialized a dense weight "
        f"({pallas_calls} decompress calls traced)")
    assert xla_calls > 0, "control: the XLA route must decompress per layer"
    return {
        "layer_bytes_packed": int(packed_bytes),
        "layer_bytes_dense": int(dense_bytes),
        "packed_over_dense": round(packed_bytes / dense_bytes, 4),
        "pallas_route_dense_materializations": pallas_calls,
        "xla_route_dense_materializations": xla_calls,
    }


def _early_exit(eng, steps: int = 32) -> Dict:
    """All-done early exit inside the decode chunk (DESIGN.md §15): once
    every row's ``done`` flag is set mid-chunk, the chunk's step loop
    stops instead of running the remaining whole-model steps. Measured
    directly on the jitted chunk: the same
    chunk timed with all rows live vs all rows already done — the gap is
    what a request that finishes early in a long chunk no longer pays."""
    from repro.models import registry

    mb = eng.max_batch
    chunk = eng._chunk_fn(steps)

    def once(done_val: bool) -> float:
        cache = registry.init_cache(eng.cfg, mb, 8 + steps + 1)
        toks = jnp.asarray(np.full((mb, 8), 7, np.int32))
        cur, cache = eng._prefill(eng.params, cache, {"tokens": toks})
        done = jnp.full((mb,), done_val)
        jax.block_until_ready(cur)
        t0 = time.perf_counter()
        out = chunk(eng.params, cache, cur, done)
        jax.block_until_ready(out[3])
        return time.perf_counter() - t0

    once(False), once(True)                      # compile, warm up
    t_live = min(once(False) for _ in range(3))
    t_done = min(once(True) for _ in range(3))
    assert t_done < t_live, (
        f"all-done chunk ({t_done:.4f}s) not faster than a live one "
        f"({t_live:.4f}s) — the early exit is not short-circuiting")
    return {"chunk_steps": steps,
            "live_chunk_s": round(t_live, 5),
            "all_done_chunk_s": round(t_done, 5),
            "skip_speedup": round(t_live / t_done, 2)}


def run(fast: bool = False) -> Dict:
    from repro.serve.engine import ServeEngine

    cfg, proj, packed = _build()
    rng = np.random.default_rng(0)
    n_req = 8 if fast else 16
    prompts, budgets = _workload(n_req, rng)
    n_waves = -(-n_req // 4)
    static_steps = n_waves * max(budgets)
    cont_steps = -(-sum(budgets) // 4)
    # eos that greedy can't emit: decode length is budget-driven, so the
    # A/B measures scheduling, not random early stops
    eng = ServeEngine(cfg, packed, max_batch=4, eos_id=-1, fetch_chunk=8)

    # warmup: compile prefill/decode/chunk paths for both schedulers
    _static_per_token(eng, prompts[:4], budgets[:4])
    eng.serve(prompts[:4], budgets[:4])

    # best-of-3: decode steps are identical run-over-run, so the best wall
    # time is the least host-noise-contaminated one (shared CI runners)
    t_static = t_cont = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out_static = _static_per_token(eng, prompts, budgets)
        t_static = min(t_static, time.perf_counter() - t0)
        t0 = time.perf_counter()
        out_cont = eng.serve(prompts, budgets)
        t_cont = min(t_cont, time.perf_counter() - t0)

    assert out_static == out_cont, "schedulers must emit identical tokens"
    n_tok = sum(len(o) for o in out_cont)
    tok_s_static = n_tok / t_static
    tok_s_cont = n_tok / t_cont
    speedup = tok_s_cont / tok_s_static
    row = {
        "n_requests": n_req,
        "max_batch": 4,
        "budgets_short_long": sorted(set(budgets)),
        "total_tokens": n_tok,
        "static_decode_steps_bound": static_steps,
        "continuous_decode_steps_bound": cont_steps,
        "static_per_token_tok_s": round(tok_s_static, 2),
        "continuous_chunked_tok_s": round(tok_s_cont, 2),
        "speedup": round(speedup, 3),
    }
    print(f"  static (per-token sync) : {tok_s_static:9.1f} tok/s")
    print(f"  continuous (chunked)    : {tok_s_cont:9.1f} tok/s "
          f"({speedup:.2f}x)")
    assert speedup >= SPEEDUP_FLOOR, (
        f"decode speedup {speedup:.2f}x below the {SPEEDUP_FLOOR}x floor")

    res = _residency(cfg, packed, proj)
    print(f"  layer weights packed/dense: {res['layer_bytes_packed']}/"
          f"{res['layer_bytes_dense']} B "
          f"({100 * res['packed_over_dense']:.1f}%), "
          f"dense materializations on streaming route: "
          f"{res['pallas_route_dense_materializations']}")
    ee = _early_exit(eng)
    print(f"  all-done early exit: live chunk {ee['live_chunk_s']*1e3:.1f}"
          f"ms vs done {ee['all_done_chunk_s']*1e3:.1f}ms "
          f"({ee['skip_speedup']:.1f}x skip)")
    return {"throughput": row, "residency": res, "early_exit": ee}


if __name__ == "__main__":
    run()
