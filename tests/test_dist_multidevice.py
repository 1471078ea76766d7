"""Multi-device semantics, each in a subprocess with virtual CPU devices
(XLA_FLAGS must not leak into the main test process — the brief requires
unit tests to see one device)."""
import json
import os
import subprocess
import sys

import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str, devices: int = 8, timeout: int = 900) -> dict:
    prog = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
import sys, json
sys.path.insert(0, {_SRC!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
{body}
print("JSON::" + json.dumps(out))
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    for line in r.stdout.splitlines():
        if line.startswith("JSON::"):
            return json.loads(line[len("JSON::"):])
    raise AssertionError(f"no JSON in output: {r.stdout[-2000:]}")


def test_vocab_parallel_ce_matches_dense():
    out = _run("""
from repro.dist.collectives import dense_ce, vocab_parallel_ce
from repro.launch.mesh import make_smoke_mesh
mesh = make_smoke_mesh(data=2, model=4)
k = jax.random.PRNGKey(0)
h = jax.random.normal(k, (4, 8, 32))
w = jax.random.normal(jax.random.fold_in(k, 1), (32, 64))
labels = jax.random.randint(jax.random.fold_in(k, 2), (4, 8), 0, 64)
mask = (jax.random.uniform(jax.random.fold_in(k, 3), (4, 8)) > 0.3).astype(jnp.float32)
with mesh:
    vp = float(vocab_parallel_ce(h, w, labels, mesh, mask))
dn = float(dense_ce(h, w, labels, mask))
# gradients must match too
with mesh:
    gv = jax.grad(lambda hh: vocab_parallel_ce(hh, w, labels, mesh, mask))(h)
gd = jax.grad(lambda hh: dense_ce(hh, w, labels, mask))(h)
out = {"vp": vp, "dn": dn,
       "gdiff": float(jnp.abs(gv - gd).max())}
""")
    assert out["vp"] == pytest.approx(out["dn"], rel=1e-5)
    assert out["gdiff"] < 1e-5


def test_vocab_parallel_embed_matches_gather():
    out = _run("""
from repro.dist.collectives import vocab_parallel_embed
from repro.launch.mesh import make_smoke_mesh
mesh = make_smoke_mesh(data=2, model=4)
k = jax.random.PRNGKey(0)
table = jax.random.normal(k, (64, 16))
toks = jax.random.randint(jax.random.fold_in(k, 1), (4, 8), 0, 64)
with mesh:
    vp = vocab_parallel_embed(table, toks, jnp.float32, mesh)
ref = table[toks]
out = {"diff": float(jnp.abs(vp - ref).max())}
""")
    assert out["diff"] < 1e-5


def test_sharded_train_step_matches_single_device():
    out = _run("""
from repro.config import RunConfig, TrainConfig
from repro.configs import get_config
from repro.dist import sharding as shd
from repro.dist.mesh_ctx import use_mesh
from repro.launch.mesh import make_smoke_mesh
from repro.train.loop import init_train_state, make_train_step
cfg = get_config("olmo-1b", smoke=True)
rc = RunConfig(model=cfg, train=TrainConfig(learning_rate=1e-3))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(0), (8, 32), 0, cfg.vocab_size),
         "labels": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size),
         "loss_mask": jnp.ones((8, 32), jnp.float32)}
# single device
state = init_train_state(jax.random.PRNGKey(0), rc)
s1, m1 = jax.jit(make_train_step(rc))(state, batch)
# sharded
mesh = make_smoke_mesh(data=2, model=4)
with use_mesh(mesh):
    state2 = init_train_state(jax.random.PRNGKey(0), rc)
    sh = shd.named_sharding_tree(shd.param_specs(state2.params, mesh, cfg), mesh)
    state2 = state2.__class__(params=jax.device_put(state2.params, sh),
                              opt_state=state2.opt_state, ef=state2.ef,
                              step=state2.step)
    s2, m2 = jax.jit(make_train_step(rc))(state2, batch)
l1 = jax.tree_util.tree_leaves(s1.params)
l2 = jax.tree_util.tree_leaves(s2.params)
diffs = [float(jnp.abs(a - b).max()) for a, b in zip(l1, l2)]
out = {"loss1": float(m1["loss"]), "loss2": float(m2["loss"]),
       "maxdiff": max(diffs)}
""")
    assert out["loss1"] == pytest.approx(out["loss2"], rel=1e-4)
    assert out["maxdiff"] < 5e-4


def test_moe_ep_matches_local():
    out = _run("""
from repro.configs import get_config
from repro.dist.mesh_ctx import use_mesh
from repro.launch.mesh import make_smoke_mesh
from repro.models.moe import moe_apply, moe_init
cfg = get_config("arctic-480b", smoke=True)
p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
cl = cfg.replace(moe=cfg.moe.__class__(num_experts=8, top_k=2,
                                       capacity_factor=8.0,
                                       dense_residual_ff=128, impl="local"))
ce = cl.replace(moe=cl.moe.__class__(num_experts=8, top_k=2,
                                     capacity_factor=8.0,
                                     dense_residual_ff=128, impl="ep"))
y_local, aux_l = moe_apply(p, cl, x)
mesh = make_smoke_mesh(data=2, model=4)
with use_mesh(mesh):
    y_ep, aux_e = jax.jit(lambda pp, xx: moe_apply(pp, ce, xx))(p, x)
out = {"diff": float(jnp.abs(y_local - y_ep).max()),
       "aux_l": float(aux_l), "aux_e": float(aux_e)}
""")
    # high capacity factor → no token dropping → paths agree
    assert out["diff"] < 1e-3
    assert out["aux_l"] == pytest.approx(out["aux_e"], rel=1e-4)


def test_pipeline_forward_matches_sequential():
    out = _run("""
from repro.dist.pipeline import pipeline_forward, stack_stages
from repro.launch.mesh import make_smoke_mesh
mesh = make_smoke_mesh(data=2, model=1, pod=4)
L, M, B, D = 8, 6, 4, 32
ws = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) / jnp.sqrt(D)
x = jax.random.normal(jax.random.PRNGKey(1), (M, B, D))

def layer(c, w):
    return jnp.tanh(c @ w), None

def stage_fn(stage_ws, xx):
    return jax.lax.scan(layer, xx, stage_ws)[0]

stages = stack_stages(ws, 4)
y_pp = pipeline_forward(stages, x, stage_fn, mesh, axis="pod")
y_seq = jax.vmap(lambda xx: jax.lax.scan(layer, xx, ws)[0])(x)
out = {"diff": float(jnp.abs(y_pp - y_seq).max())}
""")
    assert out["diff"] < 1e-5


def test_tp_gemm_bit_exact_matrix():
    """Sharded-Pallas vs single-device-Pallas vs XLA over the TP GEMM
    matrix: {int8, bf16} × {dense, DBB-packed} × {column (N) split,
    row (K) split + boundary psum} on 2- and 4-device meshes.

    Splits without a reduction (column) must be BIT-identical on every
    dtype; K-splits are bit-identical for int8 (integer accumulate —
    addition order free) and tolerance-bounded for floats (the psum
    reorders the accumulation)."""
    out = _run("""
import dataclasses
from repro.core.dbb import pack_dbb
from jax import shard_map
from repro.dist.mesh_ctx import shard_tp_ctx, use_mesh
from repro.kernels import dispatch
from repro.launch.mesh import make_smoke_mesh

M, K, N, BLOCK, NNZ = 8, 256, 256, 8, 4
k0 = jax.random.PRNGKey(0)
out = {}
for tp in (2, 4):
    mesh = make_smoke_mesh(data=1, model=tp)
    for dt_name in ("int8", "bf16"):
        if dt_name == "int8":
            x = jax.random.randint(k0, (M, K), -4, 4, jnp.int8)
            w = jax.random.randint(jax.random.fold_in(k0, 1), (K, N),
                                   -4, 4, jnp.int8)
            cases = [("dense", w)]
        else:
            x = jax.random.normal(k0, (M, K)).astype(jnp.bfloat16)
            wf = (jax.random.normal(jax.random.fold_in(k0, 1), (K, N))
                  / jnp.sqrt(K)).astype(jnp.bfloat16)
            cases = [("dense", wf), ("packed", pack_dbb(wf, BLOCK, NNZ))]
        for wname, wv in cases:
            kw = dict(out_dtype=x.dtype) if wname == "packed" else {}
            y_pal = dispatch.matmul(x, wv, pallas=True, **kw)
            y_xla = dispatch.matmul(x, wv, pallas=False, **kw)
            is_dbb = wname == "packed"
            wspec = (jax.tree_util.tree_map(lambda _: P(None, "model"), wv)
                     if is_dbb else P(None, "model"))
            with use_mesh(mesh):
                def col(xl, wl):
                    with shard_tp_ctx(tp):
                        return dispatch.matmul(xl, wl, pallas=True, **kw)
                y_col = shard_map(col, mesh=mesh,
                                  in_specs=(P(), wspec),
                                  out_specs=P(None, "model"),
                                  check_vma=False)(x, wv)
                wspec_r = (jax.tree_util.tree_map(lambda _: P("model", None),
                                                  wv)
                           if is_dbb else P("model", None))
                def row(xl, wl):
                    with shard_tp_ctx(tp):
                        y = dispatch.matmul(xl, wl, pallas=True, **kw)
                    return jax.lax.psum(y, "model")
                y_row = shard_map(row, mesh=mesh,
                                  in_specs=(P(None, "model"), wspec_r),
                                  out_specs=P(),
                                  check_vma=False)(x, wv)
            key = f"tp{tp}/{dt_name}/{wname}"
            f32 = lambda a: jnp.asarray(a, jnp.float32)
            out[key + "/col_vs_pallas"] = float(
                jnp.abs(f32(y_col) - f32(y_pal)).max())
            out[key + "/col_vs_xla"] = float(
                jnp.abs(f32(y_col) - f32(y_xla)).max())
            out[key + "/row_vs_pallas"] = float(
                jnp.abs(f32(y_row) - f32(y_pal)).max())
            out[key + "/ref_scale"] = float(jnp.abs(f32(y_pal)).max())
""", devices=4)
    for key, diff in out.items():
        if key.endswith("/ref_scale"):
            continue
        scale = out[key.rsplit("/", 1)[0] + "/ref_scale"]
        if "/int8/" in key or "/col_vs_pallas" in key:
            assert diff == 0.0, (key, diff)       # bit-identical
        else:
            assert diff <= max(scale, 1.0) * 2e-2, (key, diff, scale)


def test_tp_serve_parity_matrix():
    """The acceptance contract on a 4-device mesh: with
    ``gemm_impl="pallas"`` the engine routes prefill GEMM, skinny decode
    and flash attention through shard_map'd Pallas kernels (asserted via
    dispatch.explain), and the ragged packed-prefill serving loop is
    token-identical to single-device Pallas AND the XLA route on BOTH KV
    backends, dense and DBB-packed, whole-prompt and chunked prefill."""
    out = _run("""
from repro.config import DbbConfig, ModelConfig
from repro.core.dbb_linear import pack_tree
from repro.dist.mesh_ctx import use_mesh
from repro.kernels import dispatch
from repro.models import registry
from repro.serve.engine import ServeEngine

dbb = DbbConfig(enabled=True, block=8, nnz=4)
cfg = ModelConfig(family="dense_lm", d_model=64, d_ff=256, num_layers=2,
                  num_heads=8, num_kv_heads=4, vocab_size=128,
                  dtype="float32", gemm_impl="pallas", kv_page_size=8,
                  dbb=dbb)
params = registry.init_params(jax.random.PRNGKey(0), cfg)
packed = pack_tree(params, dbb)
prompts = [[5, 6, 7, 8], [9, 10, 11], [3, 4], [12, 13, 14, 15, 16]]
mesh = jax.make_mesh((1, 4), ("data", "model"))

out = {"streams": {}, "routes": {}, "tp_reason": {}}
for label, p in (("dense", params), ("packed", packed)):
    ref_x = ServeEngine(cfg.replace(gemm_impl="xla"), p, max_batch=4,
                        paged=False).serve(prompts, max_new_tokens=6)
    ref_p = ServeEngine(cfg, p, max_batch=4).serve(prompts,
                                                   max_new_tokens=6)
    with use_mesh(mesh):
        eng = ServeEngine(cfg, p, max_batch=4)
        out["tp_reason"][label] = eng.tp_reason
        tp_paged = eng.serve(prompts, max_new_tokens=6)
        tp_contig = ServeEngine(cfg, p, max_batch=4, paged=False).serve(
            prompts, max_new_tokens=6)
        tp_chunked = ServeEngine(cfg, p, max_batch=4,
                                 prefill_chunk=3).serve(
            prompts, max_new_tokens=6)
    out["streams"][label] = {
        "xla": ref_x, "pallas1": ref_p, "tp_paged": tp_paged,
        "tp_contig": tp_contig, "tp_chunked": tp_chunked}

# route assertions: explain() costs the per-shard instance the shard_map
# bodies run, on representative serving shapes (global dims + tp=4)
with use_mesh(mesh):
    pre = dispatch.explain("matmul", m=512, k=1024, n=4096, cfg=cfg,
                           tp=4)
    dec = dispatch.explain("matmul", m=8, k=1024, n=32768, cfg=cfg,
                           tp=4, gemv=True)
    att = dispatch.explain("attention", m=512, k=128, n=512, batch=8,
                           cfg=cfg, tp=4)
    out["routes"]["prefill_gemm"] = next(d.name for d in pre if d.chosen)
    out["routes"]["decode_gemv"] = next(d.name for d in dec if d.chosen)
    out["routes"]["attention"] = next(d.name for d in att if d.chosen)
    out["routes"]["mesh_note"] = dispatch.format_table(pre).splitlines()[0]
""", devices=4)
    for label, streams in out["streams"].items():
        ref = streams["pallas1"]
        for name, got in streams.items():
            assert got == ref, (label, name, got, ref)
    assert out["tp_reason"] == {"dense": "", "packed": ""}
    assert out["routes"]["prefill_gemm"] in ("sta", "skinny_sta")
    assert out["routes"]["decode_gemv"] in ("skinny_sta", "skinny_dbb")
    assert out["routes"]["attention"] == "attn_flash"
    assert "costed for mesh" in out["routes"]["mesh_note"]


def test_tp_greedy_vocab_parallel_heads():
    """Satellite: both vocab-parallel greedy heads — the column-sharded
    scalar-combine (`greedy_vocab_parallel`) and the `psum_scatter`
    variant (`greedy_scatter`, each hop moves [B, vocab/tp] instead of
    [B, vocab]) — match the dense argmax."""
    out = _run("""
from repro.dist.collectives import greedy_scatter, greedy_vocab_parallel
from repro.launch.mesh import make_smoke_mesh

mesh = make_smoke_mesh(data=1, model=4)
k = jax.random.PRNGKey(0)
h = jax.random.normal(k, (6, 32))
w = jax.random.normal(jax.random.fold_in(k, 1), (32, 128)) / 8.0
ref = jnp.argmax(h @ w, axis=-1)
vp = greedy_vocab_parallel(h, w, mesh)
sc = greedy_scatter(h, w, mesh)
out = {"vp": int((vp == ref).all()), "sc": int((sc == ref).all())}
""", devices=4)
    assert out["vp"] == 1
    assert out["sc"] == 1


def test_dryrun_cell_on_virtual_devices():
    """End-to-end dry-run of one smoke-sized cell on 8 devices: lower +
    compile + roofline terms present."""
    out = _run("""
from repro.config import ShapeSpec
from repro.configs import get_config
from repro.dist import sharding as shd
from repro.dist.mesh_ctx import use_mesh
from repro.launch import specs as sp
from repro.launch.mesh import make_smoke_mesh
from repro.roofline.analysis import roofline_terms
from repro.roofline.hlo import analyze_hlo_text
from repro.train.loop import make_train_step
mesh = make_smoke_mesh(data=2, model=4)
cfg = get_config("qwen2.5-14b", smoke=True)
shape = ShapeSpec("t", 64, 8, "train")
with use_mesh(mesh):
    rc = sp.run_config_for(cfg, shape)
    state_sds, state_spec = sp.train_state_specs(rc, mesh, fsdp=1 << 12)
    state_sh = shd.named_sharding_tree(state_spec, mesh)
    batch_sds = sp.train_input_specs(rc.model, shape)
    bspecs = shd.batch_specs(rc.model, mesh, 8, 64)
    batch_sh = shd.named_sharding_tree({k: bspecs.get(k, P()) for k in batch_sds}, mesh)
    step = make_train_step(rc)
    compiled = jax.jit(step, in_shardings=(state_sh, batch_sh),
                       out_shardings=(state_sh, None),
                       donate_argnums=(0,)).lower(state_sds, batch_sds).compile()
st = analyze_hlo_text(compiled.as_text())
t = roofline_terms(st, model_flops_per_device=1e9, io_bytes_per_device=1e6)
out = {"flops": st.flops, "coll": sum(st.collective_bytes.values()),
       "bottleneck": t.bottleneck}
""")
    assert out["flops"] > 0
    assert out["coll"] > 0
