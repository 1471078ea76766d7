"""Materialization lint: the shared jaxpr-walk API (DESIGN.md §13 pass 4).

The paper's memory claims are *absence* claims — the [B,H,T,S] attention
score tensor, the decompressed dense DBB weight, and the [M,K] im2col
patch matrix must never exist as whole arrays. These are provable at
trace time: walk every intermediate aval of the traced computation
(recursing into pallas/scan/cond sub-jaxprs, whose avals are the
block-sized VMEM refs) and bound the largest one. This module is the one
implementation of that walk — tests and benchmarks import it instead of
carrying private copies.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, List, Sequence, Tuple

__all__ = ["iter_avals", "trace_avals", "max_intermediate_elems",
           "max_intermediate_bytes", "assert_no_intermediate_larger_than",
           "MaterializationCheck", "run_checks"]


def iter_avals(jaxpr) -> Iterator:
    """Yield the output aval of every equation in ``jaxpr``, recursing
    into sub-jaxprs held in equation params (pallas kernel bodies,
    scan/while/cond/jit bodies, custom_vjp branches)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def subs(val):
        if isinstance(val, (Jaxpr, ClosedJaxpr)):
            yield val if isinstance(val, Jaxpr) else val.jaxpr
        elif isinstance(val, (tuple, list)):
            for v in val:
                yield from subs(v)
        elif isinstance(val, dict):
            for v in val.values():
                yield from subs(v)

    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for val in eqn.params.values():
            for sub in subs(val):
                yield from iter_avals(sub)


def trace_avals(fn: Callable, *args, **kwargs) -> List:
    """Shaped intermediate avals of ``fn(*args)`` — trace-time only, the
    function is never executed."""
    import jax
    jaxpr = jax.make_jaxpr(fn, **kwargs)(*args)
    return [a for a in iter_avals(jaxpr.jaxpr) if hasattr(a, "shape")]


def _elems(aval) -> int:
    out = 1
    for s in aval.shape:
        out *= int(s)
    return out


def max_intermediate_elems(fn: Callable, *args) -> int:
    """Largest intermediate (elements) anywhere in the traced jaxpr."""
    return max((_elems(a) for a in trace_avals(fn, *args)), default=0)


def max_intermediate_bytes(fn: Callable, *args) -> int:
    """Largest intermediate (bytes) anywhere in the traced jaxpr."""
    return max((_elems(a) * getattr(a.dtype, "itemsize", 4)
                for a in trace_avals(fn, *args)), default=0)


def assert_no_intermediate_larger_than(fn: Callable, *args,
                                       max_elems: int,
                                       what: str = "") -> int:
    """Assert no traced intermediate of ``fn(*args)`` reaches
    ``max_elems`` elements; returns the observed peak (so callers can
    additionally assert a positive control *does* cross the limit)."""
    peak = max_intermediate_elems(fn, *args)
    label = what or getattr(fn, "__name__", "fn")
    assert peak < max_elems, (
        f"{label}: materialized a {peak}-element intermediate "
        f"(limit {max_elems})")
    return peak


@dataclasses.dataclass(frozen=True)
class MaterializationCheck:
    """One no-materialization claim: ``build()`` returns ``(fn, args,
    limit_elems)``; the pass traces ``fn(*args)`` and flags any
    intermediate of ``limit_elems`` elements or more. ``build`` is lazy
    so the repo checks import models/serve only when the pass runs."""
    name: str
    describe: str
    build: Callable[[], Tuple[Callable, tuple, int]]


def run_checks(checks: Sequence[MaterializationCheck]):
    """Run materialization checks; returns (n_checked, violations)."""
    from repro.analysis.contracts import Violation
    out: List[Violation] = []
    for chk in checks:
        try:
            fn, args, limit = chk.build()
            peak = max_intermediate_elems(fn, *args)
        except Exception as e:  # a check that cannot trace is a finding
            out.append(Violation(
                pass_name="materialize", code="trace-failed",
                subject=chk.name, message=f"{type(e).__name__}: {e}"))
            continue
        if peak >= limit:
            out.append(Violation(
                pass_name="materialize", code="materialized",
                subject=chk.name,
                message=f"{chk.describe}: traced a {peak}-element "
                        f"intermediate (limit {limit})"))
    return len(checks), out


def repo_checks() -> List[MaterializationCheck]:
    """The repo's three structural absence claims (DESIGN.md §8/§9/§10)."""

    def _attn_no_score():
        import jax
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import attention as attn_mod
        cfg = get_config("olmo-1b", smoke=True).replace(
            remat="none", attn_impl="flash")
        b, t, hq, hkv, d = 2, 256, 4, 2, 32
        q = jnp.zeros((b, t, hq, d))
        k = jnp.zeros((b, t, hkv, d))
        v = jnp.zeros((b, t, hkv, d))
        pos = jnp.arange(t)[None, :]
        fn = jax.jit(lambda *a: attn_mod._attention_core(*a, cfg))
        return fn, (q, k, v, pos), b * hq * t * t

    def _dbb_no_dense():
        import jax.numpy as jnp
        from repro.core.dbb import dbb_mask, pack_dbb
        from repro.kernels import dispatch
        m, k, n = 8, 512, 512
        w = jnp.ones((k, n), jnp.float32)
        w = w * dbb_mask(w, block=8, nnz=4)
        pw = pack_dbb(w, block=8, nnz=4)
        x = jnp.zeros((m, k), jnp.float32)
        fn = lambda x: dispatch.matmul(x, pw, pallas=True)  # noqa: E731
        return fn, (x,), k * n

    def _conv_no_im2col():
        import jax.numpy as jnp
        from repro.kernels import dispatch
        b, h, w_dim, c, kh, kw = 4, 16, 16, 16, 3, 3
        n = 32
        x = jnp.zeros((b, h, w_dim, c), jnp.float32)
        w = jnp.zeros((kh * kw * c, n), jnp.float32)
        fn = (lambda x, w: dispatch.conv(x, w, kh=kh, kw=kw, stride=1,
                                         route="conv_sta"))
        # implied GEMM's M·K im2col patch matrix (SAME: ho=h, wo=w)
        return fn, (x, w), b * h * w_dim * kh * kw * c

    return [
        MaterializationCheck(
            name="attn-no-score-tensor",
            describe="flash route must not materialize the [B,Hq,T,S] "
                     "score tensor",
            build=_attn_no_score),
        MaterializationCheck(
            name="dbb-no-dense-weight",
            describe="packed DBB matmul must not expand the dense [K,N] "
                     "weight",
            build=_dbb_no_dense),
        MaterializationCheck(
            name="conv-no-im2col",
            describe="implicit-GEMM conv must not materialize the [M,K] "
                     "im2col patch matrix",
            build=_conv_no_im2col),
    ]
