"""GPipe-style microbatch pipeline over one mesh axis.

`stack_stages` splits a stacked layer tree [L, ...] into S contiguous
stages [S, L/S, ...]; `pipeline_forward` runs M microbatches through the S
stages on an S-device ring: at step t, stage s processes microbatch
t - s, and `ppermute` hands activations to stage s+1. Total steps
M + S - 1; the classic (S-1)/M bubble.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


__all__ = ["stack_stages", "pipeline_forward"]


def stack_stages(stacked: Any, n_stages: int) -> Any:
    """[L, ...] layer stacks → [S, L/S, ...] stage stacks (pytree-wide)."""
    def re(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])
    return jax.tree_util.tree_map(re, stacked)


def pipeline_forward(stages: Any, x: jax.Array,
                     stage_fn: Callable[[Any, jax.Array], jax.Array],
                     mesh, axis: str = "pod") -> jax.Array:
    """Run microbatches x [M, B, ...] through `stages` ([S, ...] trees,
    sharded over `axis`) with stage_fn(stage_weights, act) per stage.

    Returns [M, B, ...] identical (up to reduction order) to running all
    layers sequentially on one device.
    """
    s_total = mesh.shape[axis]
    m_total = x.shape[0]
    perm = [(i, i + 1) for i in range(s_total - 1)]

    def shard_fn(stage_local, xs):
        # stage_local: [1, ...] slice of the stage stack — drop the axis dim
        ws = jax.tree_util.tree_map(lambda a: a[0], stage_local)
        sidx = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        for t in range(m_total + s_total - 1):
            m = t - sidx                       # this stage's microbatch id
            first_in = xs[jnp.clip(m, 0, m_total - 1)]
            inp = jnp.where(sidx == 0, first_in, buf)
            y = stage_fn(ws, inp)
            live = (m >= 0) & (m < m_total) & (sidx == s_total - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(live, y, outs[jnp.clip(m, 0, m_total - 1)]),
                jnp.clip(m, 0, m_total - 1), 0)
            buf = jax.lax.ppermute(y, axis, perm)
        # only the last stage holds real outputs; psum replicates them
        outs = jnp.where(sidx == s_total - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    return shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False)(stages, x)
