"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Single-host entry point that composes every substrate layer: config →
synthetic data pipeline → (optional) virtual mesh → DBB-annealed train loop
→ checkpointing → fault tolerance. The same loop body is what the dry-run
lowers for the production meshes.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Optional

import jax
import numpy as np

from repro.config import RunConfig, ShapeSpec, TrainConfig
from repro.configs import get_config
from repro.core.sparsity import dbb_schedule_nnz, tree_sparsity_report
from repro.data.pipeline import make_pipeline
from repro.dist import sharding as shd
from repro.dist.mesh_ctx import use_mesh
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_smoke_mesh
from repro.train import checkpoint as ckpt
from repro.train.fault_tolerance import (PreemptionGuard, StragglerMonitor,
                                         retry_step)
from repro.train.loop import init_train_state, make_train_step

__all__ = ["train_loop", "main"]


def train_loop(run_cfg: RunConfig, shape: ShapeSpec, mesh=None,
               log=print, host_index: int = 0, host_count: int = 1):
    """Returns (final TrainState, list of metric dicts)."""
    cfg = run_cfg.model
    tcfg = run_cfg.train
    pipe = make_pipeline(cfg, shape, seed=tcfg.seed, host_index=host_index,
                         host_count=host_count)
    mgr = (ckpt.CheckpointManager(tcfg.checkpoint_dir, tcfg.checkpoint_every)
           if tcfg.checkpoint_dir else None)
    monitor = StragglerMonitor()
    history = []

    def build_state():
        return init_train_state(jax.random.PRNGKey(tcfg.seed), run_cfg)

    ctx = use_mesh(mesh) if mesh is not None else None
    if ctx is not None:
        ctx.__enter__()
    try:
        state = build_state()
        start_step = 0
        if mgr is not None and ckpt.latest_step(tcfg.checkpoint_dir) is not None:
            state, meta = ckpt.restore(tcfg.checkpoint_dir, state)
            start_step = meta["step"]
            log(f"resumed from step {start_step}")

        if mesh is not None:
            pspecs = shd.param_specs(state.params, mesh, cfg)
            sh = shd.named_sharding_tree(pspecs, mesh)
            state = state.__class__(
                params=jax.device_put(state.params, sh),
                opt_state=state.opt_state, ef=state.ef, step=state.step)

        jit_cache = {}

        def step_fn_for(nnz: Optional[int]):
            if nnz not in jit_cache:
                jit_cache[nnz] = jax.jit(make_train_step(run_cfg, nnz=nnz),
                                         donate_argnums=(0,))
            return jit_cache[nnz]

        with PreemptionGuard() as guard:
            for step in range(start_step, tcfg.steps):
                t0 = time.time()
                nnz = dbb_schedule_nnz(cfg.dbb, step, tcfg.dbb_prune_start,
                                       tcfg.dbb_prune_ramp)
                batch = {k: jax.numpy.asarray(v)
                         for k, v in pipe.batch_at(step).items()}
                fn = step_fn_for(nnz if cfg.dbb.enabled else None)
                state, metrics = retry_step(lambda: fn(state, batch))
                dt = time.time() - t0
                straggler = monitor.update(step, dt)
                if step % max(tcfg.log_every, 1) == 0 or straggler:
                    m = {k: float(v) for k, v in metrics.items()}
                    m.update(step=step, dt=round(dt, 3), nnz=nnz,
                             straggler=straggler)
                    history.append(m)
                    log(json.dumps(m))
                if mgr is not None:
                    mgr.maybe_save(step, state, {"dt": dt})
                if guard.should_stop:
                    log("preemption signal: emergency checkpoint")
                    if mgr is not None:
                        mgr.maybe_save(step, state, {"preempted": True},
                                       force=True)
                    break
        if mgr is not None:
            mgr.maybe_save(tcfg.steps, state, force=True)
        if monitor.straggler_steps:
            log(f"stragglers flagged: {monitor.straggler_steps} "
                f"(mean step {monitor.mean_step_time:.3f}s)")
        return state, history
    finally:
        if ctx is not None:
            ctx.__exit__(None, None, None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress", default="none")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--dense", action="store_true", help="disable DBB")
    ap.add_argument("--dbb-ramp", type=int, default=0)
    ap.add_argument("--mesh", default="none",
                    help="none | dxm (e.g. 2x4) virtual mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.dense:
        cfg = cfg.replace(dbb=cfg.dbb.__class__(enabled=False))
    run_cfg = RunConfig(model=cfg, train=TrainConfig(
        steps=args.steps, learning_rate=args.lr, optimizer=args.optimizer,
        microbatches=args.microbatches, grad_compress=args.grad_compress,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every, seed=args.seed,
        dbb_prune_ramp=args.dbb_ramp))
    shape = ShapeSpec("cli", args.seq_len, args.batch, "train")
    mesh = None
    if args.mesh != "none":
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_smoke_mesh(data=d, model=m)
    state, history = train_loop(run_cfg, shape, mesh=mesh)
    if cfg.dbb.enabled:
        rep = tree_sparsity_report(state.params, cfg.dbb)
        nz = {k: round(v, 3) for k, v in list(rep.items())[:5]}
        print("sparsity (first 5 leaves):", json.dumps(nz))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
