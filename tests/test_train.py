"""Training substrate: optimizers, compression, checkpointing, convergence,
fault tolerance."""
import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import RunConfig, ShapeSpec, TrainConfig
from repro.configs import get_config
from repro.data.pipeline import SyntheticCNN, SyntheticLM, make_pipeline
from repro.launch.train import train_loop
from repro.train import checkpoint as ckpt
from repro.train.fault_tolerance import (PreemptionGuard, StragglerMonitor,
                                         retry_step)
from repro.train.grad_compress import compress_grads, init_ef_state
from repro.train.loop import init_train_state, make_train_step
from repro.train.optimizer import (clip_by_global_norm, global_norm,
                                   lr_schedule, make_optimizer)


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

def _quadratic_losses(opt_name, steps=60, lr=0.1):
    cfg = TrainConfig(optimizer=opt_name, learning_rate=lr, warmup_steps=2,
                      steps=steps, weight_decay=0.0)
    init, update = make_optimizer(cfg)
    target = jnp.array([[1.0, -2.0], [3.0, 0.5]])
    # nonzero init: adafactor's relative step scales with RMS(param)
    params = {"w": jnp.full((2, 2), 0.5)}
    state = init(params)
    losses = []
    for s in range(steps):
        loss, grads = jax.value_and_grad(
            lambda p: ((p["w"] - target) ** 2).sum())(params)
        ups, state = update(grads, state, params, jnp.asarray(s))
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, ups)
        losses.append(float(loss))
    return losses


@pytest.mark.parametrize("opt", ["adamw", "adafactor", "sgd"])
def test_optimizer_converges_on_quadratic(opt):
    losses = _quadratic_losses(opt)
    assert losses[-1] < 0.05 * losses[0], (opt, losses[0], losses[-1])


def test_adafactor_state_is_factored():
    cfg = TrainConfig(optimizer="adafactor")
    init, _ = make_optimizer(cfg)
    params = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((64,))}
    st = init(params)
    assert st["s"]["w"]["vr"].shape == (64,)
    assert st["s"]["w"]["vc"].shape == (32,)
    assert st["s"]["b"]["v"].shape == (64,)


def test_clip_by_global_norm():
    tree = {"a": jnp.full((10,), 3.0), "b": jnp.full((10,), 4.0)}
    clipped, gn = clip_by_global_norm(tree, 1.0)
    assert float(gn) == pytest.approx(np.sqrt(250.0))
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


def test_lr_schedule_warmup_cosine():
    cfg = TrainConfig(learning_rate=1.0, warmup_steps=10, steps=100)
    f = lr_schedule(cfg)
    assert float(f(jnp.asarray(0))) < 0.2
    assert float(f(jnp.asarray(10))) == pytest.approx(1.0, rel=0.1)
    assert float(f(jnp.asarray(99))) < 0.2


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_bf16_compression_roundtrip():
    g = {"w": jnp.array([1.0, 1e-3, 256.5])}
    out, _ = compress_grads(g, None, "bf16")
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               rtol=1e-2)


def test_int8_error_feedback_compensates():
    """With EF the *accumulated* applied gradient tracks the true sum even
    though each step quantizes aggressively."""
    rng = np.random.default_rng(0)
    true = jnp.asarray(rng.normal(size=(256,)).astype(np.float32))
    ef = init_ef_state({"w": true}, "int8_ef")
    applied = jnp.zeros_like(true)
    for s in range(20):
        sent, ef = compress_grads({"w": true}, ef, "int8_ef")
        applied = applied + sent["w"]
    np.testing.assert_allclose(np.asarray(applied) / 20, np.asarray(true),
                               atol=np.abs(true).max() / 100)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    cfg = get_config("olmo-1b", smoke=True)
    rc = RunConfig(model=cfg, train=TrainConfig())
    state = init_train_state(jax.random.PRNGKey(0), rc)
    d = str(tmp_path / "ck")
    ckpt.save(d, 7, state)
    template = init_train_state(jax.random.PRNGKey(1), rc)
    restored, meta = ckpt.restore(d, template)
    assert meta["step"] == 7
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomic_and_pruned(tmp_path):
    d = str(tmp_path / "ck")
    state = {"w": jnp.arange(8.0)}
    for s in (1, 2, 3, 4, 5):
        ckpt.save(d, s, state, keep_last=2)
    assert ckpt.available_steps(d) == [4, 5]
    assert ckpt.latest_step(d) == 5
    # template mismatch is rejected, not silently mis-restored
    with pytest.raises(ValueError):
        ckpt.restore(d, {"w": jnp.zeros((8,)), "extra": jnp.zeros((2,))})


def test_resume_is_bit_exact(tmp_path):
    """10 straight steps == 5 steps + checkpoint + restore + 5 steps."""
    cfg = get_config("lenet5-dbb", smoke=True)
    shape = ShapeSpec("t", 16, 8, "train")

    def run(steps, ckdir=None, resume=False):
        rc = RunConfig(model=cfg, train=TrainConfig(
            steps=steps, learning_rate=1e-2, log_every=1,
            checkpoint_dir=ckdir or "", checkpoint_every=0, seed=3))
        return train_loop(rc, shape, log=lambda *_: None)

    s_straight, _ = run(10)
    d = str(tmp_path / "ck")
    rc5 = RunConfig(model=cfg, train=TrainConfig(
        steps=5, learning_rate=1e-2, checkpoint_dir=d, seed=3, log_every=1))
    s5, _ = train_loop(rc5, shape, log=lambda *_: None)
    ckpt.save(d, 5, s5)
    rc10 = RunConfig(model=cfg, train=TrainConfig(
        steps=10, learning_rate=1e-2, checkpoint_dir=d, seed=3, log_every=1))
    s_resumed, _ = train_loop(rc10, shape, log=lambda *_: None)
    for a, b in zip(jax.tree_util.tree_leaves(s_straight.params),
                    jax.tree_util.tree_leaves(s_resumed.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# convergence (end-to-end loop)
# ---------------------------------------------------------------------------

def test_cnn_training_converges():
    cfg = get_config("convnet-dbb", smoke=True)
    rc = RunConfig(model=cfg, train=TrainConfig(
        steps=30, learning_rate=3e-3, log_every=1, dbb_prune_start=10,
        dbb_prune_ramp=10))
    shape = ShapeSpec("t", 16, 32, "train")
    state, hist = train_loop(rc, shape, log=lambda *_: None)
    assert hist[-1]["loss"] < hist[0]["loss"] * 0.8
    assert hist[-1]["nnz"] == cfg.dbb.nnz        # anneal reached the bound


def test_lm_training_converges():
    cfg = get_config("olmo-1b", smoke=True)
    rc = RunConfig(model=cfg, train=TrainConfig(
        steps=25, learning_rate=1e-3, log_every=1))
    shape = ShapeSpec("t", 32, 8, "train")
    state, hist = train_loop(rc, shape, log=lambda *_: None)
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_microbatched_grads_match_full_batch():
    cfg = get_config("olmo-1b", smoke=True)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0,
                                          cfg.vocab_size),
             "labels": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                          cfg.vocab_size),
             "loss_mask": jnp.ones((4, 16), jnp.float32)}
    outs = {}
    for m in (1, 2):
        rc = RunConfig(model=cfg, train=TrainConfig(microbatches=m))
        state = init_train_state(jax.random.PRNGKey(0), rc)
        new_state, metrics = jax.jit(make_train_step(rc))(state, batch)
        outs[m] = (new_state, metrics)
    np.testing.assert_allclose(float(outs[1][1]["loss"]),
                               float(outs[2][1]["loss"]), rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(outs[1][0].params),
                    jax.tree_util.tree_leaves(outs[2][0].params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_and_skippable():
    cfg = get_config("olmo-1b", smoke=True)
    shape = ShapeSpec("t", 32, 8, "train")
    p1 = SyntheticLM(cfg, shape, seed=5)
    p2 = SyntheticLM(cfg, shape, seed=5)
    for s in (0, 3, 100):       # stateless: arbitrary order, same data
        np.testing.assert_array_equal(p1.batch_at(s)["tokens"],
                                      p2.batch_at(s)["tokens"])
    assert not np.array_equal(p1.batch_at(0)["tokens"],
                              p1.batch_at(1)["tokens"])


def test_pipeline_host_sharding_partitions_batch():
    cfg = get_config("olmo-1b", smoke=True)
    shape = ShapeSpec("t", 16, 8, "train")
    full = SyntheticLM(cfg, shape, seed=9, host_index=0, host_count=1)
    parts = [SyntheticLM(cfg, shape, seed=9, host_index=i, host_count=4)
             for i in range(4)]
    sizes = [p.batch_at(0)["tokens"].shape[0] for p in parts]
    assert sizes == [2, 2, 2, 2]
    # hosts draw disjoint streams (host index enters the seed)
    assert not np.array_equal(parts[0].batch_at(0)["tokens"],
                              parts[1].batch_at(0)["tokens"])


def test_labels_are_shifted_tokens():
    cfg = get_config("olmo-1b", smoke=True)
    shape = ShapeSpec("t", 32, 4, "train")
    b = SyntheticLM(cfg, shape, seed=0).batch_at(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
    assert b["loss_mask"][:, -1].sum() == 0


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_preemption_guard_catches_sigterm():
    with PreemptionGuard(signals=(signal.SIGUSR1,)) as g:
        assert not g.should_stop
        os.kill(os.getpid(), signal.SIGUSR1)
        time.sleep(0.05)
        assert g.should_stop


def test_straggler_monitor_flags_outliers():
    m = StragglerMonitor(threshold=2.0, warmup=3)
    flagged = [m.update(i, 0.1) for i in range(10)]
    assert not any(flagged)
    assert m.update(10, 0.5)
    assert m.straggler_steps == 1
    # outlier did not poison the mean
    assert m.mean_step_time == pytest.approx(0.1, rel=0.05)


def test_retry_step_retries_then_succeeds():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return 42

    assert retry_step(flaky, retries=3, backoff_s=0.0) == 42
    assert len(calls) == 3


def test_retry_step_exhausts():
    with pytest.raises(RuntimeError):
        retry_step(lambda: (_ for _ in ()).throw(RuntimeError("x")),
                   retries=1, backoff_s=0.0)


def test_compile_cache_location(monkeypatch, tmp_path):
    """Entry points keep JAX's persistent compile cache where
    JAX_COMPILATION_CACHE_DIR says (setting nothing themselves), else at
    <checkout>/.jax_cache."""
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, ".jax_cache")
    assert compile_cache.use_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
