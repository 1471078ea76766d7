"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Initializes (or restores) weights, optionally DBB-packs them (compressed
HBM residency — the paper's deployment mode), and runs batched greedy
generation over synthetic prompts, reporting the weight-footprint saving.
``--requests N`` (N > batch) drives the continuous-batching scheduler
instead of one static batch: requests admit into free slots between
decode chunks (DESIGN.md §9). ``--attn-backend`` picks the attention
implementation (flash = fused Pallas kernels, DESIGN.md §10) and
``--kv-page-size`` / ``--kv-pool-pages`` serve through the paged KV cache
(admission by pages actually used instead of a max_len reserve per slot).
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import get_config
from repro.core.dbb_linear import pack_tree, tree_footprint_bytes
from repro.launch.compile_cache import use_compile_cache
from repro.models import registry
from repro.serve.engine import ServeEngine

__all__ = ["main"]


def _log_routes(cfg, batch: int, smax: int, packed: bool,
                total_tokens: int = 0, sampling_on: bool = False,
                use_tt: bool = False) -> None:
    """Print the dispatch registry's ranked route tables (DESIGN.md §11)
    for this serving run's hot shapes — decode-batch layer GEMM, prefill
    attention at the shape the engine actually dispatches, and decode
    attention at the *actual* cache length — so the serve log shows *why*
    each kernel runs. ``smax`` and the page derivation mirror
    `decode_attention_apply` exactly (gcd-adaptive page when kv_page_size
    is unset); a fabricated shape here could log a route the engine never
    takes. ``total_tokens > 0`` means packed admission: prefill is charged
    at the ragged batch's real token count (one cu_seqlens call), not the
    padded B×T_max rectangle the legacy scheduler would dispatch."""
    import math

    import jax.numpy as jnp

    from repro.kernels import dispatch
    from repro.kernels.attn import DEFAULT_PAGE

    d, ff = cfg.d_model, cfg.d_ff
    hd = cfg.resolved_head_dim
    print(f"\nkernel routes (gemm_impl={cfg.gemm_impl!r}, "
          f"attn_impl={cfg.attn_impl!r}, overrides="
          f"{dict(cfg.kernel_routes) or 'none'}):")
    w4 = packed and cfg.dbb.weight_bits == 4
    w4_kw = dict(bits=4, group=cfg.dbb.quant_group) if w4 else {}
    print(f"- decode layer GEMM [M={batch}, K={d}, N={ff}]"
          f"{' packed w4' if w4 else ' packed' if packed else ''}:")
    print(dispatch.format_table(dispatch.explain(
        "matmul", m=batch, k=d, n=ff, dtype=cfg.dtype, packed=packed,
        cfg=cfg, epilogue_ops=1, **w4_kw)))  # the MLP GEMMs fuse 1 act/scale
    if total_tokens > 0:
        print(f"- prefill attention [total_tokens={total_tokens}, "
              f"packed cu_seqlens]:")
        print(dispatch.format_table(dispatch.explain(
            "attention", m=total_tokens, k=hd, n=total_tokens,
            dtype=cfg.dtype, cfg=cfg, packed_seq=True)))
    else:
        print(f"- prefill attention [B={batch}, T_max={smax}, padded]:")
        print(dispatch.format_table(dispatch.explain(
            "attention", m=smax, k=hd, n=smax, dtype=cfg.dtype, cfg=cfg,
            batch=batch)))
    if sampling_on:
        print(f"- head sample [M={batch}, K={d}, N={cfg.vocab_size}]"
              f"{' (top-k/top-p active)' if use_tt else ''}:")
        print(dispatch.format_table(dispatch.explain(
            "head_sample", m=batch, k=d, n=cfg.vocab_size,
            dtype=cfg.dtype, cfg=cfg, sample_tt=use_tt)))
    g = cfg.num_heads // max(1, cfg.num_kv_heads)
    page = cfg.kv_page_size or math.gcd(smax, DEFAULT_PAGE)
    route = dispatch.decode_attention_route(
        cfg, group=g, head_dim=hd,
        itemsize=jnp.dtype(cfg.dtype).itemsize, page=page, smax=smax,
        kv_heads=cfg.num_kv_heads)
    print(f"- decode attention (G={g}, smax={smax}, page={page}): "
          f"{route}\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--packed", action="store_true",
                    help="serve DBB-packed weights")
    ap.add_argument("--weight-bits", type=int, default=0,
                    choices=[0, 4, 8],
                    help="packed value-plane width (with --packed): 4 = "
                         "nibble-packed INT4 + groupwise scales, the "
                         "decode bandwidth floor (DESIGN.md §16); 8 = "
                         "INT8/float plane; 0 = the arch config's "
                         "dbb.weight_bits")
    ap.add_argument("--quant-group", type=int, default=0,
                    help="w4 scale-group length G along K (0 = the arch "
                         "config's dbb.quant_group, default 128)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=0,
                    help="total request count; > batch engages the "
                         "continuous-batching scheduler (default: one "
                         "static batch)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--attn-backend", default=None,
                    choices=["auto", "flash", "chunked", "naive"],
                    help="attention backend override (DESIGN.md §10); "
                         "default: the arch config's attn_impl")
    ap.add_argument("--kv-page-size", type=int, default=0,
                    help="KV page size in cache slots; > 0 serves through "
                         "the paged KV cache (block-table flash decode, "
                         "admission by pages used)")
    ap.add_argument("--kv-pool-pages", type=int, default=0,
                    help="physical page pool size (with --kv-page-size); "
                         "0 = contiguous-cache HBM parity")
    ap.add_argument("--prefill-mode", default="packed",
                    choices=["packed", "padded"],
                    help="prompt admission: 'packed' concatenates the "
                         "ragged batch into one cu_seqlens prefill call "
                         "(no pad rows in any GEMM, DESIGN.md §12); "
                         "'padded' is the legacy per-row rectangle")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: split prompts into chunks of "
                         "this many tokens so long prompts interleave "
                         "with decode steps (bounds TTFT jitter); 0 = "
                         "whole-prompt prefill (packed mode only)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for every request "
                         "(0 = greedy, bit-identical to the legacy "
                         "argmax path; DESIGN.md §15)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation (0 = off; any truncation "
                         "pins the head to the XLA sampler route)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus truncation (1.0 = off)")
    ap.add_argument("--draft-k", type=int, default=0,
                    help="self-speculative decode: draft this many "
                         "tokens per step with the truncated-layer "
                         "model, verify in one batched step (0 = off; "
                         "incompatible with top-k/top-p)")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.weight_bits or args.quant_group:
        import dataclasses as _dc
        dbb = cfg.dbb
        dbb = _dc.replace(
            dbb,
            weight_bits=args.weight_bits or dbb.weight_bits,
            quant_group=args.quant_group or dbb.quant_group)
        cfg = cfg.replace(dbb=dbb)
    if args.attn_backend:
        cfg = cfg.replace(attn_impl=args.attn_backend)
    if args.kv_page_size:
        cfg = cfg.replace(kv_page_size=args.kv_page_size)
    elif args.kv_pool_pages and cfg.kv_page_size <= 0:
        raise SystemExit("--kv-pool-pages only takes effect with paged "
                         "serving (--kv-page-size, or a config that sets "
                         "kv_page_size); without it the contiguous cache "
                         "ignores the pool budget")
    if cfg.family == "cnn" or cfg.embeds_input or cfg.prefix_embed_len:
        raise SystemExit(f"{args.arch}: token-decoder serving only "
                         "(modality frontends are stubs)")
    params = registry.init_params(jax.random.PRNGKey(args.seed), cfg)
    dense_bytes = tree_footprint_bytes(params)
    if args.packed and cfg.dbb.enabled:
        from repro.core.sparsity import apply_dbb_to_tree
        params = apply_dbb_to_tree(params, cfg.dbb, straight_through=False)
        params = pack_tree(params, cfg.dbb)
        packed_bytes = tree_footprint_bytes(params)
        print(f"weight footprint: dense {dense_bytes/1e6:.1f} MB -> packed "
              f"{packed_bytes/1e6:.1f} MB "
              f"({100*packed_bytes/dense_bytes:.1f}%)")

    rng = np.random.default_rng(args.seed)
    n_req = args.requests or args.batch
    prompts = [list(rng.integers(2, cfg.vocab_size,
                                 size=args.prompt_len))
               for _ in range(n_req)]
    # generate() caches prompt+budget slots; serve() buckets to powers of
    # two — log the generate()-shaped cache length (the common case);
    # "packed" only when the weights actually are (--packed AND dbb on).
    # Packed admission charges prefill at the first wave's real token
    # count (sum over admitted prompts), not the B×T_max rectangle.
    sampled = (args.temperature > 0.0 or args.top_k > 0
               or args.top_p < 1.0 or args.draft_k > 0)
    sampling = None
    if sampled:
        from repro.serve.sampling import SamplingParams
        sampling = [SamplingParams(temperature=args.temperature,
                                   top_k=args.top_k, top_p=args.top_p,
                                   seed=args.seed + i)
                    for i in range(n_req)]
    use_tt = args.top_k > 0 or args.top_p < 1.0
    wave = sum(len(p) for p in prompts[:args.batch])
    _log_routes(cfg, args.batch, args.prompt_len + args.max_new,
                packed=bool(args.packed and cfg.dbb.enabled),
                total_tokens=wave if args.prefill_mode == "packed" else 0,
                sampling_on=sampled, use_tt=use_tt)
    if sampled:
        print(f"sampling: temperature={args.temperature} "
              f"top_k={args.top_k} top_p={args.top_p} "
              f"seeds={args.seed}..{args.seed + n_req - 1} (per request); "
              f"speculative draft_k={args.draft_k}"
              + (" (draft = first num_layers//2 layers, rejection-"
                 "sampling verify)" if args.draft_k else " (off)"))
    eng = ServeEngine(cfg, params, max_batch=args.batch,
                      kv_pool_pages=args.kv_pool_pages,
                      prefill_mode=args.prefill_mode,
                      prefill_chunk=args.prefill_chunk,
                      draft_k=args.draft_k)
    if n_req > args.batch:
        outs = eng.serve(prompts, max_new_tokens=args.max_new,
                         sampling=sampling)
    else:
        outs = eng.generate(prompts, max_new_tokens=args.max_new,
                            sampling=sampling)
    for i, o in enumerate(outs):
        print(f"req{i}: {o}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
