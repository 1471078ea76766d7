#!/usr/bin/env python3
"""Chip smoke test: DBB-packed serving on a TPU v5e through the Pallas route.

    python chip_smoke.py           # one chip: olmo-1b at published widths
    python chip_smoke.py --tp 4    # four chips: qwen2.5-14b, TP-sharded

Run it from the repository root on a machine with a TPU v5e. It drives the
entry points a user calls — ``registry.init_params`` → ``apply_dbb_to_tree``
→ ``pack_tree`` → ``ServeEngine.serve`` — on random weights made from
``--seed``, and checks what comes out:

* every phase serves its requests through the Pallas routes: the dispatch
  registry's picks are recorded and any fallback route or warning fails
  the run;
* every request decodes its full token budget;
* the last-position prefill logits of the serving path agree with the same
  weights on the XLA route within a tolerance fixed per dtype.

One chip, olmo-1b ``full()`` (16 × d2048, d_ff 8192, vocab 50304), weights
in bf16, three phases:

1. DBB-packed: 8 requests through 4 slots, ragged prompts of 16–200
   tokens, 16 new tokens each, packed chunked prefill, paged KV; then 4 of
   them again sampled at temperature 0.8 (fused sampling head);
2. INT4 DBB (4-bit groupwise values, group 128): 4 requests;
3. dense Pallas control: 4 requests.

``--tp 4`` runs only the tensor-parallel path on one host's four chips:
qwen2.5-14b ``full()`` (48 × d5120, GQA 40/8, d_ff 13824, vocab 152064),
DBB-packed, weights created and packed already sharded on a 1×4
("data", "model") mesh, served by ``ServeEngine`` under that mesh and
compared with the same weights on the GSPMD XLA route.

Without a TPU v5e (``jax.devices()[0].platform != "tpu"``) it exits
non-zero before doing anything. The earlier lines report each phase's
first-call (compile included) and warm seconds, tokens and routes; the
last line is ``{"ok": true, "device": {...}}``. No figure here is a
benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
V5E_KINDS = ("TPU v5 lite", "TPU v5e")   # device_kind strings of a v5e chip
SLOTS = 4
MAX_NEW = 16
PREFILL_CHUNK = 64
KV_PAGE = 64
# max |pallas - xla| over max |xla| of the last-position logits, fixed per
# compute dtype before any run: bf16 keeps 8 mantissa bits (2^-8 ≈ 0.004
# per rounding) and the two routes round at different points through every
# layer; a wrong layout or head permutation moves logits by O(1)
LOGIT_TOL = {"bfloat16": 5e-2, "float32": 1e-3}
# routes that are fallbacks away from the Pallas kernels
FALLBACK_ROUTES = {"attention": {"attn_naive", "attn_chunked",
                                 "attn_packed_ref"},
                   "attn_decode": {"attn_decode_xla"},
                   "head_sample": {"head_sample_xla"},
                   "conv": {"conv_xla"}}


def device_summary(chips: int) -> dict:
    """The device as JAX reports it; refuses anything but TPU v5e."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0].platform is "
                         f"{d.platform!r}")
    if d.device_kind not in V5E_KINDS:
        raise SystemExit(f"device_kind {d.device_kind!r} is not a TPU v5e: "
                         "the roofline peaks and VMEM budgets assume v5e")
    if len(devs) < chips:
        raise SystemExit(f"needs {chips} chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def ragged_prompts(n: int, vocab: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = rng.integers(16, 201, size=n)
    return [[int(t) for t in rng.integers(2, vocab, size=int(ln))]
            for ln in lens]


def _peak_gb() -> float:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


def _check_warnings(caught, phase: str) -> None:
    """Any warning attributed to the repository's code (its own frames or,
    through ``stacklevel``, this script's calls into it) is a fallback or
    a degraded path: fail the phase."""
    ours = [w for w in caught if os.path.abspath(w.filename).startswith(ROOT)]
    for w in caught:
        print(f"  warning ({phase}): {w.category.__name__}: {w.message}")
    if ours:
        raise RuntimeError(f"{phase}: the run warned — "
                           + "; ".join(str(w.message) for w in ours))


def _check_routes(routes, phase: str, expect, allow_xla_matmul: bool):
    picked = {r for _, r in routes}
    bad = sorted(f"{d}:{r}" for d, r in routes
                 if r in FALLBACK_ROUTES.get(d, ())
                 or (d == "matmul" and r == "xla" and not allow_xla_matmul))
    if bad:
        raise RuntimeError(f"{phase}: fallback routes taken: {bad}")
    missing = sorted(set(expect) - picked)
    if missing:
        raise RuntimeError(f"{phase}: expected routes never taken: "
                           f"{missing} (took {sorted(picked)})")


def _check_tokens(outs, n_req: int, vocab: int, phase: str) -> int:
    if len(outs) != n_req:
        raise RuntimeError(f"{phase}: {len(outs)} outputs for {n_req} "
                           "requests")
    for i, o in enumerate(outs):
        if len(o) != MAX_NEW or not all(0 <= t < vocab for t in o):
            raise RuntimeError(f"{phase}: request {i} returned {o}")
    return sum(len(o) for o in outs)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def serve_phase(phase: str, cfg, params, prompts, *, expect,
                sampled: int = 0, allow_xla_matmul: bool = False,
                max_batch: int = SLOTS) -> None:
    """Serve ``prompts`` (and ``sampled`` of them again at temperature 0.8)
    on the Pallas route, check routes, tokens, warnings and the prefill
    logits against the XLA route on the same weights. Raises on any
    failure."""
    import numpy as np

    from repro.kernels import dispatch
    from repro.serve.engine import ServeEngine
    from repro.serve.sampling import SamplingParams

    eos = cfg.vocab_size          # never emitted: every request runs its budget
    report = {"phase": phase}
    with warnings.catch_warnings(record=True) as caught, \
            dispatch.record_routes() as routes:
        warnings.simplefilter("always")
        eng = ServeEngine(cfg, params, max_batch=max_batch, eos_id=eos,
                          prefill_chunk=PREFILL_CHUNK)
        report["tp_reason"] = eng.tp_reason
        outs, report["first_call_s"] = _timed(
            lambda: eng.serve(prompts, max_new_tokens=MAX_NEW))
        again, report["warm_s"] = _timed(
            lambda: eng.serve(prompts, max_new_tokens=MAX_NEW))
        if again != outs:
            raise RuntimeError(f"{phase}: greedy serving is not "
                               "deterministic across two identical calls")
        report["tokens"] = _check_tokens(outs, len(prompts), cfg.vocab_size,
                                          phase)
        report["prompt_tokens"] = sum(len(p) for p in prompts)
        for i, o in enumerate(outs):
            print(f"  {phase} req{i} ({len(prompts[i])} prompt tokens): {o}")
        if sampled:
            sp = [SamplingParams(temperature=0.8, seed=i)
                  for i in range(sampled)]
            souts, report["sampled_first_call_s"] = _timed(
                lambda: eng.serve(prompts[:sampled], max_new_tokens=MAX_NEW,
                                  sampling=sp))
            _, report["sampled_warm_s"] = _timed(
                lambda: eng.serve(prompts[:sampled], max_new_tokens=MAX_NEW,
                                  sampling=sp))
            report["sampled_tokens"] = _check_tokens(
                souts, sampled, cfg.vocab_size, phase + "/sampled")
            for i, o in enumerate(souts):
                print(f"  {phase} sampled req{i} (T=0.8): {o}")
        probe = prompts[:max_batch]
        got, report["logits_first_call_s"] = _timed(
            lambda: eng.prefill_logits(probe))
        del eng
    report["routes"] = sorted(f"{d}:{r}" for d, r in routes)
    _check_routes(routes, phase, expect, allow_xla_matmul)

    with warnings.catch_warnings(record=True) as caught_ref:
        warnings.simplefilter("always")
        ref_eng = ServeEngine(cfg.replace(gemm_impl="xla"), params,
                              max_batch=max_batch, eos_id=eos)
        ref = ref_eng.prefill_logits(probe)
        del ref_eng
    _check_warnings(list(caught) + list(caught_ref), phase)

    if got.shape != (len(probe), cfg.vocab_size) or \
            not np.all(np.isfinite(got)):
        raise RuntimeError(f"{phase}: prefill logits {got.shape} not finite "
                           "or mis-shaped")
    tol = LOGIT_TOL[cfg.dtype]
    err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    report.update(logit_rel_err=err, logit_tol=tol,
                  argmax_agree=int(np.sum(got.argmax(-1) == ref.argmax(-1))),
                  argmax_of=len(probe), peak_hbm_gb=_peak_gb())
    print(json.dumps(report))
    if not err <= tol:
        raise RuntimeError(f"{phase}: prefill logits differ from the XLA "
                           f"route by {err:.3g} (relative) > {tol}")


def one_chip(cfg, seed: int) -> None:
    """The three olmo-1b phases (module docstring) for ``cfg``."""
    import dataclasses

    import jax

    from repro.core.dbb_linear import pack_tree
    from repro.core.sparsity import apply_dbb_to_tree
    from repro.models import registry

    cfg = cfg.replace(gemm_impl="pallas", kv_page_size=KV_PAGE,
                      param_dtype=cfg.dtype)
    prompts = ragged_prompts(2 * SLOTS, cfg.vocab_size, seed)
    attn = ("attn_packed_flash", "attn_flash", "attn_decode_flash")

    params = registry.init_params(jax.random.PRNGKey(seed), cfg)
    serve_phase("dense", cfg, params, prompts[:SLOTS],
                expect=("sta", "skinny_sta") + attn, allow_xla_matmul=True)

    projected = apply_dbb_to_tree(params, cfg.dbb, straight_through=False)
    del params
    packed = pack_tree(projected, cfg.dbb)
    w4_dbb = dataclasses.replace(cfg.dbb, weight_bits=4, quant_group=128)
    packed_w4 = pack_tree(projected, w4_dbb)
    del projected

    serve_phase("dbb_packed", cfg, packed, prompts, sampled=SLOTS,
                expect=("dbb_packed", "skinny_dbb", "head_sample_fused")
                + attn)
    del packed
    serve_phase("dbb_w4", cfg.replace(dbb=w4_dbb), packed_w4,
                prompts[:SLOTS],
                expect=("dbb_packed_w4", "skinny_dbb_w4") + attn)


def sharded_packed_params(cfg, mesh, seed: int):
    """Random DBB-packed params for ``cfg``, created and packed already
    sharded on ``mesh``: one jitted step per layer initializes that layer,
    projects and packs it, and writes it into the donated, sharded stacked
    tree — no device ever holds the full model, dense or packed."""
    import jax
    import jax.numpy as jnp

    from repro.core.dbb_linear import pack_tree
    from repro.core.sparsity import apply_dbb_to_tree
    from repro.dist.sharding import named_sharding_tree, param_specs
    from repro.models import registry

    def make(key, c):
        p = registry.init_params(key, c)
        p = apply_dbb_to_tree(p, c.dbb, straight_through=False)
        return pack_tree(p, c.dbb)

    key = jax.random.PRNGKey(seed)
    one = cfg.replace(num_layers=1)
    abstract = jax.eval_shape(lambda: make(key, cfg))
    shardings = named_sharding_tree(
        param_specs(abstract, mesh, cfg, fsdp_min_shard_elems=None), mesh)

    @jax.jit
    def rest():
        return make(key, one)

    def first(r):
        out = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                           abstract["layers"])
        return dict(r, layers=out)

    params = jax.jit(lambda: first(rest()), out_shardings=shardings)()

    def fill(p, layer):
        new = make(jax.random.fold_in(key, layer), one)["layers"]
        layers = jax.tree.map(
            lambda s, n: jax.lax.dynamic_update_index_in_dim(s, n[0], layer,
                                                             0),
            p["layers"], new)
        return dict(p, layers=layers)

    fill = jax.jit(fill, donate_argnums=0, out_shardings=shardings)
    for layer in range(cfg.num_layers):
        params = fill(params, jnp.int32(layer))
    return params


def tensor_parallel(cfg, tp: int, seed: int) -> None:
    """The ``--tp`` phase (module docstring) for ``cfg``."""
    import jax

    from repro.dist.mesh_ctx import use_mesh

    cfg = cfg.replace(gemm_impl="pallas", kv_page_size=KV_PAGE,
                      param_dtype=cfg.dtype)
    with use_mesh(jax.make_mesh((1, tp), ("data", "model"))) as mesh:
        params, build_s = _timed(
            lambda: jax.block_until_ready(
                sharded_packed_params(cfg, mesh, seed)))
        print(f"  sharded packed params built in {build_s:.1f} s, peak "
              f"{_peak_gb():.2f} GB on device 0")
        from repro.serve.engine import tp_serve_reason
        reason = tp_serve_reason(cfg, mesh, params)
        if reason:
            raise RuntimeError(f"TP wrap refused: {reason}")
        serve_phase(f"tp{tp}_dbb_packed", cfg, params,
                    ragged_prompts(2 * SLOTS, cfg.vocab_size, seed),
                    expect=("dbb_packed", "skinny_dbb", "attn_packed_flash",
                            "attn_decode_flash"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip olmo-1b phases; 4: only the "
                         "four-chip tensor-parallel qwen2.5-14b phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = device_summary(args.tp)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    print(f"device: {device}; compile cache: {use_compile_cache()}")
    if args.tp == 1:
        one_chip(get_config("olmo-1b"), args.seed)
    else:
        tensor_parallel(get_config("qwen2.5-14b"), args.tp, args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
