"""Sharding-rule policy tests (pure spec logic — no devices needed).

Guarantee checked here: every PartitionSpec produced for every assigned
architecture divides evenly on the production meshes, so the dry-run can
never fail on a divisibility error.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import SHAPES, RunConfig, TrainConfig
from repro.configs import ASSIGNED, get_config
from repro.dist import sharding as shd
from repro.models import registry

# spec-only "mesh": shape dict + axis names are all the rules consult
class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)

    def __hash__(self):
        return hash(tuple(self.shape.items()))


POD = _FakeMesh({"data": 16, "model": 16})
MULTI = _FakeMesh({"pod": 2, "data": 16, "model": 16})


def _axsize(mesh, entry):
    if entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _check_divisible(tree_specs, tree_vals, mesh, where=""):
    flat_s = jax.tree_util.tree_flatten_with_path(
        tree_specs, is_leaf=lambda x: isinstance(x, P))[0]
    flat_v = jax.tree_util.tree_flatten_with_path(tree_vals)[0]
    specs = {"/".join(str(p) for p in path): s for path, s in flat_s}
    for path, leaf in flat_v:
        key = "/".join(str(p) for p in path)
        spec = specs.get(key, P())
        if not isinstance(spec, P) or not hasattr(leaf, "shape"):
            continue
        for dim, entry in zip(leaf.shape, tuple(spec)):
            n = _axsize(mesh, entry)
            assert dim % n == 0, (where, key, leaf.shape, spec)


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("mesh", [POD, MULTI], ids=["pod", "multipod"])
def test_param_specs_divide_for_full_configs(arch, mesh):
    cfg = get_config(arch)          # FULL config — abstract init only
    sds = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg))
    specs = shd.param_specs(sds, mesh, cfg)
    _check_divisible(specs, sds, mesh, where=arch)


def test_column_and_row_rules():
    cfg = get_config("olmo-1b")
    sds = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg))
    specs = shd.param_specs(sds, POD, cfg, fsdp_min_shard_elems=None)
    lyr = specs["layers"]
    assert tuple(lyr["attn"]["q_proj"]["w"]) == (None, None, "model")
    assert tuple(lyr["attn"]["o_proj"]["w"]) == (None, "model", None)
    assert tuple(lyr["mlp"]["wi"]["w"]) == (None, None, "model")
    assert tuple(lyr["mlp"]["wo"]["w"]) == (None, "model", None)
    assert tuple(specs["embed"]["table"]) == ("model", None)


def test_expert_rule_and_fsdp():
    cfg = get_config("kimi-k2-1t-a32b")
    sds = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg))
    specs = shd.param_specs(sds, POD, cfg)
    wi = tuple(specs["layers"]["moe"]["experts"]["wi"])
    # [L, E, d, f]: experts on model, FSDP data on a free dim
    assert wi[1] == "model"
    assert "data" in (wi[2], wi[3], wi[0])


def test_fsdp_disabled_keeps_small_replicated():
    cfg = get_config("yi-34b")      # rmsnorm => has replicated scale leaves
    sds = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg))
    specs = shd.param_specs(sds, POD, cfg, fsdp_min_shard_elems=None)
    scale = specs["layers"]["ln_attn"]["scale"]
    assert all(e is None for e in tuple(scale))
    # with FSDP on, big leaves gain a data axis; norms stay replicated
    specs_fsdp = shd.param_specs(sds, POD, cfg)
    wi = tuple(specs_fsdp["layers"]["mlp"]["wi"]["w"])
    assert any(e == "data" or (isinstance(e, tuple) and "data" in e)
               for e in wi)
    scale2 = specs_fsdp["layers"]["ln_attn"]["scale"]
    assert all(e is None for e in tuple(scale2))


def test_opt_state_specs_derivation():
    cfg = get_config("yi-34b")
    rc = RunConfig(model=cfg, train=TrainConfig(optimizer="adafactor"))
    from repro.train.loop import init_train_state
    sds = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), rc))
    pspecs = shd.param_specs(sds.params, POD, cfg)
    ospecs = shd.opt_state_specs_like(sds.opt_state, sds.params, pspecs, POD)
    _check_divisible(ospecs, sds.opt_state, POD, where="yi-opt")
    # factored stats follow the param's surviving axes
    wi_p = tuple(pspecs["layers"]["mlp"]["wi"]["w"])     # [L, d, f]
    vr = tuple(ospecs["s"]["layers"]["mlp"]["wi"]["w"]["vr"])  # [L, d]
    assert vr[:2] == wi_p[:2] or vr[1] in ("data", ("pod", "data"), None)


def test_cache_specs_match_cache_tree():
    for arch in ("qwen2.5-14b", "rwkv6-1.6b", "zamba2-1.2b"):
        cfg = get_config(arch)
        sds = jax.eval_shape(lambda c=cfg: registry.init_cache(c, 128, 32768))
        specs = shd.cache_specs(cfg, POD, 128, 32768)
        assert set(specs) == set(sds)
        _check_divisible(specs, sds, POD, where=arch)


def test_batch_specs_partial_batch():
    cfg = get_config("olmo-1b")
    # batch=1 can't shard: falls back to replication, never errors
    s = shd.batch_specs(cfg, MULTI, 1, 128)
    assert tuple(s["tokens"])[0] is None
    # batch=32 on pod×data=32 shards fully
    s = shd.batch_specs(cfg, MULTI, 32, 128)
    assert tuple(s["tokens"])[0] == ("pod", "data")


def test_zero_spec_adds_data_axes():
    spec = shd.zero_spec(P(None, None, "model"), (48, 5120, 13824), POD)
    assert "data" in tuple(spec)
    # small leaves untouched
    assert tuple(shd.zero_spec(P(), (64,), POD)) == ()


def test_use_mesh_takes_explicit_axes_as_auto():
    """`jax.make_mesh` builds Explicit axes by default; the model code
    shards with constraints and shard_map, so `use_mesh` serves the same
    devices and axis names with Auto axes."""
    from jax.sharding import AxisType

    from repro.dist.mesh_ctx import current_mesh, use_mesh
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Explicit,) * 2)
    with use_mesh(mesh) as m:
        assert current_mesh() is m
        assert m.axis_types == (AxisType.Auto, AxisType.Auto)
        assert m.axis_names == mesh.axis_names
        assert (m.devices == mesh.devices).all()
    assert current_mesh() is None
