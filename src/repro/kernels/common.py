"""Shared Pallas kernel utilities (TPU target, interpret-mode on CPU)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.sta import KERNEL_VMEM_BUDGET, SUBLANE, VMEM_BYTES

CompilerParams = pltpu.CompilerParams

__all__ = ["pltpu", "CompilerParams", "on_cpu", "default_interpret",
           "cdiv", "round_up", "popcount_u32", "acc_dtype_for",
           "SKINNY_M_MAX", "skinny_ok", "skinny_dispatch",
           "coerce_bias_scale", "pad_cols",
           "KERNEL_VMEM_BUDGET", "SKINNY_RESIDENT_BUDGET"]


def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def default_interpret() -> bool:
    """Pallas TPU kernels run in interpret mode on this CPU container."""
    return on_cpu()


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def popcount_u32(x: jax.Array, bits: int) -> jax.Array:
    """Population count via an unrolled shift-and-add (Pallas-safe: no
    dependence on lax.population_count lowering inside Mosaic)."""
    out = jnp.zeros_like(x)
    for t in range(bits):
        out = out + ((x >> t) & 1)
    return out


def coerce_bias_scale(bias, scale):
    """Epilogue contract (DESIGN.md §7): bias/scale rows are f32 no matter
    what dtype the caller's params are stored in (bf16 model trees hand
    over bf16 biases) — coerce at the wrapper boundary, before jit/tuning
    sees the operand, so one compiled kernel serves every param dtype.
    The single shared copy of the coercion all three GEMM-family ops
    wrappers (sta_gemm / dbb_gemm / conv_gemm) apply."""
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32)
    if scale is not None:
        scale = jnp.asarray(scale, jnp.float32)
    return bias, scale


def pad_cols(a, extra: int):
    """Zero-pad the last dim of a 2-D operand — weights / bias / scale /
    bitmask all share the N-padding treatment (shared shape policy)."""
    if a is None or extra == 0:
        return a
    return jnp.pad(a, ((0, 0), (0, extra)))


def acc_dtype_for(operand_dtype) -> jnp.dtype:
    """Accumulator dtype on the PE datapath: INT32 for INT8 operands
    (the paper's datapath), f32 otherwise."""
    if operand_dtype == jnp.int8:
        return jnp.dtype(jnp.int32)
    return jnp.dtype(jnp.float32)


# ---------------------------------------------------------------------------
# skinny (decode-shaped) dispatch guard — shared by the GEMM ops wrappers
# and the flash-attention decode kernel's M-gate (DESIGN.md §9/§10)
# ---------------------------------------------------------------------------

# Dispatch cap: decode/serving batches. Above this the M-tiled kernels win
# (the resident A block would crowd out weight streaming double-buffers).
SKINNY_M_MAX = 32

# Named headroom fractions (DESIGN.md §13). KERNEL_VMEM_BUDGET bounds a
# kernel's whole single-buffered working set (defined next to VMEM_BYTES in
# core.sta; re-exported here as the guards' import surface).
# SKINNY_RESIDENT_BUDGET bounds just the grid-constant resident [M, K]
# block of the skinny kernels: a quarter of VMEM, so the streamed weight
# tiles keep their double buffers even at the largest admitted K. The
# analysis verifier asserts the dispatch guards agree with these constants
# (repro.analysis.vmem), so don't respell them as VMEM_BYTES // n literals.
SKINNY_RESIDENT_BUDGET = VMEM_BYTES // 4


def skinny_ok(m: int, k: int, itemsize: int) -> bool:
    """Whether the resident-row-block (skinny) regime applies: M small
    enough and the full padded [M, K] block fits comfortably in VMEM next
    to the streamed operand's double buffers. Used for the skinny GEMM
    kernels (K = d_model) and as the attn decode kernel's M-gate
    (M = GQA group size, K = head_dim)."""
    if m > SKINNY_M_MAX:
        return False
    mp = round_up(max(m, 1), SUBLANE)
    kp = round_up(max(k, 1), 128)
    return mp * kp * itemsize <= SKINNY_RESIDENT_BUDGET


def skinny_dispatch(m: int, k: int, itemsize: int, *pinned) -> bool:
    """The guard both GEMM ops wrappers share: GEMV-shaped call (skinny
    regime) AND no caller-pinned block shape (a nonzero pinned block opts
    out of automatic skinny dispatch)."""
    return not any(pinned) and skinny_ok(m, k, itemsize)
