"""Dry-run compile sweep: ``python -m repro.launch.dryrun [...]``.

A CPU-only tool. It forces 512 virtual host devices before JAX starts and
lowers each (arch, shape, mesh) cell's step programs against a virtual
production mesh, recording roofline terms per cell; ``--all`` runs one
child process per cell. Do not run it on a machine with a TPU: one chip
serves one process, and the cells' processes would contend for it.
Kernel compiles for the real chip live in ``tests/test_v5e_compile.py``.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- the two lines above MUST run before any other import (jax locks the
# --- device count at first init); everything else follows.
import argparse          # noqa: E402
import json              # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.config import SHAPES, shape_applicable  # noqa: E402
from repro.configs import ASSIGNED, get_config  # noqa: E402
from repro.dist import sharding as shd  # noqa: E402
from repro.dist.mesh_ctx import use_mesh  # noqa: E402
from repro.launch import specs as sp  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.roofline.analysis import (model_flops_per_step,  # noqa: E402
                                     roofline_terms)
from repro.roofline.hlo import (analyze_hlo_text,  # noqa: E402
                                cpu_upcast_param_bytes)
from repro.serve.engine import make_decode_step, make_prefill_step  # noqa: E402
from repro.train.loop import make_train_step  # noqa: E402

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")


def _cell_id(arch: str, shape: str, mesh: str, packed: bool,
             int8: bool = False) -> str:
    sfx = ("__dbb_int8" if int8 else "__dbb") if packed else ""
    return f"{arch}__{shape}__{mesh}{sfx}"


def _mem_stats(compiled) -> Dict[str, Any]:
    out = {}
    try:
        ma = compiled.memory_analysis()
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            out[f] = int(getattr(ma, f, 0) or 0)
        out["total_per_device"] = (out.get("argument_size_in_bytes", 0)
                                   + out.get("output_size_in_bytes", 0)
                                   + out.get("temp_size_in_bytes", 0)
                                   - out.get("alias_size_in_bytes", 0))
    except Exception as e:           # pragma: no cover
        out["error"] = repr(e)
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             packed: bool = False, int8: bool = False,
             fsdp: Optional[int] = None,
             headpad: bool = True, verbose: bool = True) -> Dict[str, Any]:
    mesh_name = "multipod" if multi_pod else "pod"
    cfg = get_config(arch)
    orig_cfg = cfg          # MODEL_FLOPS counts the *published* arch only
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "packed": packed, "int8": int8,
        "cell": _cell_id(arch, shape_name, mesh_name, packed, int8),
    }
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    fsdp_elems = fsdp if fsdp is not None else shd.FSDP_MIN_SHARD_ELEMS
    if headpad:
        cfg = sp.pad_attention_heads(cfg, mesh.shape["model"])
        rec["head_pad"] = cfg.num_heads != orig_cfg.num_heads
    t0 = time.time()
    data_shards = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            data_shards *= mesh.shape[a]
    with use_mesh(mesh):
        if shape.kind == "train":
            rc = sp.run_config_for(cfg, shape, data_shards=data_shards,
                                   model_shards=mesh.shape.get("model", 1))
            state_sds, state_spec = sp.train_state_specs(rc, mesh,
                                                         fsdp=fsdp_elems)
            state_sh = shd.named_sharding_tree(state_spec, mesh)
            batch_sds = sp.train_input_specs(rc.model, shape)
            bspecs = shd.batch_specs(rc.model, mesh, shape.global_batch,
                                     shape.seq_len)
            batch_sh = shd.named_sharding_tree(
                {k: bspecs.get(k, P()) for k in batch_sds}, mesh)
            step = make_train_step(rc)
            jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_sds, batch_sds)
            tokens_per_step = shape.global_batch * shape.seq_len
            train = True
        else:
            packed_eff = packed and cfg.dbb.enabled
            params_sds, pspec = sp.serve_param_specs(cfg, mesh,
                                                     packed=packed_eff,
                                                     int8=int8,
                                                     fsdp=fsdp_elems)
            params_sh = shd.named_sharding_tree(pspec, mesh)
            cell = sp.input_specs(cfg, shape, mesh)
            cache_sh = shd.named_sharding_tree(cell["specs"]["cache"], mesh)
            tok_sh = shd.named_sharding_tree(cell["specs"]["tokens"], mesh)
            if shape.kind == "decode":
                step = make_decode_step(cfg)
                jitted = jax.jit(step, in_shardings=(params_sh, cache_sh,
                                                     tok_sh),
                                 out_shardings=(None, cache_sh),
                                 donate_argnums=(1,))
                lowered = jitted.lower(params_sds, cell["cache"],
                                       cell["tokens"])
                tokens_per_step = shape.global_batch
            else:
                step = make_prefill_step(cfg)
                jitted = jax.jit(step, in_shardings=(params_sh, cache_sh,
                                                     tok_sh),
                                 out_shardings=(None, cache_sh),
                                 donate_argnums=(1,))
                lowered = jitted.lower(params_sds, cell["cache"],
                                       cell["tokens"])
                tokens_per_step = shape.global_batch * shape.seq_len
            train = False

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = _mem_stats(compiled)
    cost = {k: float(v) for k, v in (compiled.cost_analysis() or {}).items()
            if isinstance(v, (int, float))}
    hlo_text = compiled.as_text()
    stats = analyze_hlo_text(hlo_text)
    # XLA:CPU legalization artifact: hoisted f32 copies of bf16 weights.
    # A TPU compile allocates none of these (bf16 is MXU-native).
    upcast = cpu_upcast_param_bytes(hlo_text)
    mem["cpu_upcast_bytes"] = upcast
    mem["temp_adjusted"] = mem.get("temp_size_in_bytes", 0) - upcast
    mem["total_adjusted"] = mem.get("total_per_device", 0) - upcast
    mf_total = model_flops_per_step(orig_cfg.active_param_count(),
                                    tokens_per_step, train)
    # HBM lower bound: read all args; write non-aliased outputs; aliased
    # (donated) outputs are rewritten fully by train/prefill (params / cache
    # fill) but only one token-slice per step by decode.
    args_b = mem.get("argument_size_in_bytes", 0)
    out_b = mem.get("output_size_in_bytes", 0)
    alias_b = mem.get("alias_size_in_bytes", 0)
    if shape.kind == "decode":
        alias_write = alias_b / max(shape.seq_len, 1)
    else:
        alias_write = alias_b
    io_bytes = args_b + max(out_b - alias_b, 0) + alias_write
    terms = roofline_terms(stats, model_flops_per_device=mf_total / n_dev,
                           io_bytes_per_device=io_bytes)

    rec.update({
        "status": "ok",
        "devices": n_dev,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem,
        "cost_analysis": {k: cost[k] for k in ("flops", "bytes accessed")
                          if k in cost},
        "hlo_stats": {
            "flops": stats.flops,
            "hbm_bytes": stats.hbm_bytes,
            "collective_bytes": stats.collective_bytes,
            "collective_counts": stats.collective_counts,
            "top_collectives": stats.top_collectives(12),
        },
        "roofline": terms.as_dict(),
        "tokens_per_step": tokens_per_step,
    })
    if verbose:
        print(f"== {rec['cell']} ==")
        print("memory_analysis:", json.dumps(mem))
        print("cost_analysis:", json.dumps(rec["cost_analysis"]))
        print("roofline:", json.dumps(terms.as_dict()))
    return rec


def _artifact_path(cell: str) -> str:
    os.makedirs(ART_DIR, exist_ok=True)
    return os.path.join(ART_DIR, f"{cell}.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES),
                    help="one shape (default: all four)")
    ap.add_argument("--mesh", default="both",
                    choices=("pod", "multipod", "both"))
    ap.add_argument("--packed", action="store_true",
                    help="serve cells with DBB-packed weights")
    ap.add_argument("--int8", action="store_true",
                    help="with --packed: INT8 values + per-channel scales")
    ap.add_argument("--fsdp", type=int, default=None,
                    help="FSDP min-shard-elems override")
    ap.add_argument("--no-headpad", dest="headpad", action="store_false",
                    help="disable TP attention-head padding (baseline mode)")
    ap.add_argument("--jobs", type=int, default=2,
                    help="parallel subprocesses in --all mode")
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--force", action="store_true",
                    help="recompute cells that already have artifacts")
    ap.add_argument("--inline", action="store_true",
                    help="run cells in-process (single cell debugging)")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else list(ASSIGNED)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = (["pod", "multipod"] if args.mesh == "both" else [args.mesh])

    cells = [(a, s, m) for a in archs for s in shapes for m in meshes]
    single = len(cells) == 1

    if single or args.inline:
        code = 0
        for a, s, m in cells:
            try:
                rec = run_cell(a, s, m == "multipod", packed=args.packed,
                               int8=args.int8, fsdp=args.fsdp,
                               headpad=args.headpad)
            except Exception:
                rec = {"arch": a, "shape": s, "mesh": m, "status": "error",
                       "cell": _cell_id(a, s, m, args.packed),
                       "error": traceback.format_exc()}
                print(rec["error"], file=sys.stderr)
                code = 1
            with open(_artifact_path(rec["cell"]), "w") as f:
                json.dump(rec, f, indent=1)
        return code

    # orchestrator mode: one subprocess per cell (isolation + parallelism)
    procs: Dict[str, subprocess.Popen] = {}
    pending = list(cells)
    failures = []
    done = 0

    def launch(a, s, m):
        cell = _cell_id(a, s, m, args.packed, args.int8)
        path = _artifact_path(cell)
        if not args.force and os.path.exists(path):
            return None
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", a, "--shape", s, "--mesh", m]
        if args.packed:
            cmd.append("--packed")
        if args.int8:
            cmd.append("--int8")
        if args.fsdp is not None:
            cmd += ["--fsdp", str(args.fsdp)]
        if not args.headpad:
            cmd.append("--no-headpad")
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        return subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                                stderr=subprocess.PIPE)

    t_start = time.time()
    while pending or procs:
        while pending and len(procs) < args.jobs:
            a, s, m = pending.pop(0)
            cell = _cell_id(a, s, m, args.packed, args.int8)
            p = launch(a, s, m)
            if p is None:
                done += 1
                print(f"[cached] {cell}")
            else:
                procs[cell] = p
        for cell, p in list(procs.items()):
            rc = p.poll()
            if rc is None:
                if time.time() - t_start > args.timeout * len(cells):
                    p.kill()
                continue
            _, err = p.communicate()
            del procs[cell]
            done += 1
            path = _artifact_path(cell)
            status = "?"
            if os.path.exists(path):
                with open(path) as f:
                    status = json.load(f).get("status", "?")
            if rc != 0 or status == "error":
                failures.append(cell)
                print(f"[FAIL {done}/{len(cells)}] {cell}\n"
                      f"{err.decode()[-2000:]}")
            else:
                print(f"[ok {done}/{len(cells)}] {cell} ({status})")
        time.sleep(0.5)

    print(f"\n{done} cells, {len(failures)} failures: {failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
