"""Fused head-sample kernel: skinny head GEMV + penalty → temperature →
Gumbel-sample epilogue in one pass (DESIGN.md §15).

Structure is the skinny weight-streaming template
(`kernels/skinny/kernel.py`): the whole [M, K] hidden block is
VMEM-resident, weight tiles stream over an (N, K) grid with K innermost.
The difference is the output — instead of materialising [M, vocab]
logits in HBM, each final-K step runs the sampling epilogue on its
accumulator tile (penalties from the streamed counts tile, temperature
scale, counter-hash Gumbel noise at *global* vocab ids) and folds the
tile into the running (best score, best index) output pair. Only those
[M, 1] scalars are ever written out.

The kernel returns BOTH the winning score and the (local) index: under
vocab-parallel TP each shard runs it on its vocab slice (noise offset by
``base`` so draws are keyed to global ids) and the scalar pair feeds the
same all-gather max/argmax combine the greedy head uses — bit-exact with
a single-device run over the full row.

Both grid dims are "arbitrary": the running-argmax output is carried
across N tiles, so tiles must arrive in ascending-j order — which is
also what makes the strict ``>`` update reproduce ``jnp.argmax``'s
first-max tie-break exactly. Every epilogue op is shared with the XLA
reference sampler (`ref.sample_scores`), which is what the dispatch
guard's bit-exactness claim rests on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sta import SUBLANE
from repro.kernels.common import (SKINNY_M_MAX, CompilerParams,
                                  pltpu, round_up)
from repro.kernels.sample.ref import NEG_INF, SALT_TOKEN, sample_scores

__all__ = ["head_sample_fused_pallas"]


def _head_sample_kernel(x_ref, w_ref, c_ref, t_ref, rep_ref, pres_ref,
                        freq_ref, seed_ref, step_ref, base_ref,
                        ov_ref, oi_ref, acc_ref, *, n_k: int,
                        block_k: int, block_n: int):
    j = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when((j == 0) & (k == 0))
    def _init_best():
        ov_ref[...] = jnp.full_like(ov_ref, NEG_INF)
        oi_ref[...] = jnp.zeros_like(oi_ref)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[:, pl.ds(k * block_k, block_k)]
    acc_ref[...] += jax.lax.dot_general(
        x, w_ref[...], dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(k == n_k - 1)
    def _sample_tile():
        m = acc_ref.shape[0]
        loc = j * block_n + jax.lax.broadcasted_iota(
            jnp.int32, (m, block_n), 1)
        score = sample_scores(
            acc_ref[...], c_ref[...], t_ref[...], rep_ref[...],
            pres_ref[...], freq_ref[...], seed_ref[...], step_ref[...],
            base_ref[...] + loc, salt=SALT_TOKEN)
        tile_best = jnp.max(score, axis=1, keepdims=True)
        tile_arg = jnp.argmax(score, axis=1).astype(jnp.int32)[:, None] \
            + j * block_n
        # Strict > keeps the earlier (lower-index) tile on ties — the
        # cross-tile analogue of argmax's first-max rule.
        better = tile_best > ov_ref[...]
        ov_ref[...] = jnp.where(better, tile_best, ov_ref[...])
        oi_ref[...] = jnp.where(better, tile_arg, oi_ref[...])


def head_sample_fused_pallas(
    x: jax.Array,        # [M, K] f32 hidden rows — fully resident
    w: jax.Array,        # [K, N] f32 head weight — streamed
    counts: jax.Array,   # [M, N] i32 output-token history counts
    temp: jax.Array,     # [M, 1] f32
    rep: jax.Array,      # [M, 1] f32
    pres: jax.Array,     # [M, 1] f32
    freq: jax.Array,     # [M, 1] f32
    seed: jax.Array,     # [M, 1] i32 per-row seed (bit pattern)
    step: jax.Array,     # [M, 1] i32 per-row emitted-token counter
    base: jax.Array,     # [M, 1] i32 global vocab id of column 0
    *,
    block_k: int = 128,
    block_n: int = 128,
    interpret: bool = False,
):
    """Returns (best score [M, 1] f32, sampled LOCAL index [M, 1] i32);
    the [M, N] logits never leave VMEM."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % SUBLANE == 0 and m <= round_up(SKINNY_M_MAX, SUBLANE), m
    assert k % block_k == 0 and n % block_n == 0, (
        f"shape ({m},{k},{n}) not divisible by blocks "
        f"({block_k},{block_n}); pad at the ops layer")
    assert counts.shape == (m, n), counts.shape
    for name, arr in (("temp", temp), ("rep", rep), ("pres", pres),
                      ("freq", freq), ("seed", seed), ("step", step),
                      ("base", base)):
        assert arr.shape == (m, 1), (name, arr.shape)
    n_k = k // block_k

    row_spec = pl.BlockSpec((m, 1), lambda j, kk: (0, 0))
    kernel = functools.partial(_head_sample_kernel, n_k=n_k,
                               block_k=block_k, block_n=block_n)
    return pl.pallas_call(
        kernel,
        name="head_sample_fused",
        grid=(n // block_n, n_k),
        in_specs=[
            pl.BlockSpec((m, k), lambda j, kk: (0, 0)),      # resident x
            pl.BlockSpec((block_k, block_n), lambda j, kk: (kk, j)),
            pl.BlockSpec((m, block_n), lambda j, kk: (0, j)),  # counts
            row_spec, row_spec, row_spec, row_spec,          # t/rep/pres/freq
            row_spec, row_spec, row_spec,                    # seed/step/base
        ],
        out_specs=(pl.BlockSpec((m, 1), lambda j, kk: (0, 0)),
                   pl.BlockSpec((m, 1), lambda j, kk: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((m, 1), jnp.float32),
                   jax.ShapeDtypeStruct((m, 1), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((m, block_n), jnp.float32)],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, w, counts, temp, rep, pres, freq, seed, step, base)
