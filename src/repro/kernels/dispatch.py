"""Unified kernel dispatch: one route registry + roofline-informed
selection across every GEMM / conv / attention subsystem (DESIGN.md §11).

After PRs 1-4 the repro had five parallel kernel subsystems (`sta_gemm`,
`dbb_gemm`, `skinny`, `conv_gemm`, `attn`) whose dispatch guards, padding
policy, and XLA fallbacks were re-implemented privately at every model
call site. This module is the single place where route decisions live:

  * a **registry** of `Route` entries per domain (``matmul`` / ``conv`` /
    ``attention`` / ``attn_decode``), each declaring an applicability
    *guard* (shape / dtype / VMEM — subsuming the scattered `skinny_ok` /
    `flash_ok` / pinned-block checks) and a *cost estimate* built from the
    same terms as `roofline/analysis.py`: FLOPs at the op's padded M/N/K
    against `Hardware.peak_flops`, bytes moved against `Hardware.hbm_bw`;
  * **front doors** `matmul` / `conv` / `attention` that run the chosen
    route with one shared shape policy (pad → run → unpad and f32
    bias/scale coercion live in the ops wrappers via `kernels.common`);
  * **overrides**: ``ModelConfig.kernel_routes`` pins a route per domain
    from config, and the ``REPRO_FORCE_ROUTE`` env var pins one globally
    (``skinny_sta`` or ``matmul=skinny_sta,conv=conv_xla``). A forced
    route whose guard rejects the op falls back to auto with a warning —
    forcing can change *which kernel* runs, never whether the op is legal;
  * `explain` returns the full ranked route table with per-route cost
    terms so tests, benchmarks and ``launch.serve`` logs can show *why* a
    route was chosen.

Selection rule: among applicable (non-deferred) routes pick the lowest
modeled cost; costs within ``COST_TIE_RTOL`` are a tie and the route with
the lower ``priority`` number (the more specialized kernel) wins. This
keeps the decision roofline-driven where the model can discriminate
(skinny vs M-tiled padding waste, compressed vs dense weight bytes,
fused vs round-tripped epilogues) and deterministic where it cannot.

Route selection runs at trace time on static shapes — inside a jit it is
resolved once per compiled shape, exactly like the old inline guards.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
import os
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.dbb import DbbWeight
from repro.kernels.common import SKINNY_M_MAX, round_up, skinny_ok
from repro.roofline.analysis import HW_V5E, Hardware, collective_bw

__all__ = [
    "Route", "RouteDecision", "OpSpec", "register_route", "routes_for",
    "select", "explain", "format_table", "matmul", "conv", "attention",
    "head_sample", "decode_attention_route", "pallas_route_active",
    "flash_backend_active", "forced_route", "routes_from_cfg",
    "record_routes", "FORCE_ROUTE_ENV", "COST_TIE_RTOL", "DOMAINS",
]

FORCE_ROUTE_ENV = "REPRO_FORCE_ROUTE"
# Relative cost window treated as a tie (the roofline model is first-order;
# within it the more specialized kernel wins on priority).
COST_TIE_RTOL = 0.10

DOMAINS = ("matmul", "conv", "attention", "attn_decode", "head_sample")

_MASK_BYTES = 1          # DBB bitmask storage: 1 byte per 8-block
_F32 = 4


# ---------------------------------------------------------------------------
# op description
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class OpSpec:
    """Static description of one op instance (everything guards and cost
    models may consult — plain ints/bools so specs hash and build at trace
    time).

    GEMM domains use (m, k, n) literally; attention maps T→m, D→k, S→n
    (and the decode domain G→m, D→k, Smax→n).
    """
    domain: str
    m: int
    k: int
    n: int
    itemsize: int = 4            # operand bytes (activations / q)
    out_itemsize: int = 4
    packed: bool = False         # weight is a DbbWeight
    block: int = 8               # DBB geometry (packed ops)
    nnz: int = 4
    vals_itemsize: int = 1       # packed value bytes (int8 deployment)
    bits: int = 8                # value-plane width (4 = nibble-packed)
    group: int = 0               # w4 scale group along dense K (bits=4)
    epilogue_ops: int = 0        # unfused bias/act/scale passes on XLA
    pallas: bool = False         # fused Pallas route family is active
    dense_fused: bool = True     # call site opted dense weights into kernels
    pinned: bool = False         # caller-pinned block shapes (no skinny)
    gemv: bool = False           # decode head GEMV: stream or stay on XLA
    float_ok: bool = True        # operand dtype the Pallas kernels accept
    # conv extras: (b, h, w, c, kh, kw, stride[, padding]) — padding
    # defaults to "SAME" for 7-tuple specs
    conv_geom: Tuple[Any, ...] = ()
    # attention extras
    ragged: bool = False
    chunk: int = 1024
    flash_active: bool = False
    # packed cu_seqlens batch: m/n are TOTAL tokens across the ragged batch,
    # not a per-row T — the padded-batch routes must not claim these
    packed_seq: bool = False
    # rows in a padded batch (the vmapped leading dim the per-row (t, s)
    # cost must scale by; packed specs keep batch=1 since m already IS the
    # whole batch's token count)
    batch: int = 1
    # decode extras (the kernel reads one page of every KV head per step)
    page: int = 0
    kv_heads: int = 1
    ring: bool = False
    # head_sample extras: top-k/top-p active for some row — they are
    # global order statistics, which the streaming fused epilogue cannot
    # compute (the XLA sampler materializes the row and sorts)
    sample_tt: bool = False
    # TP sharding (DESIGN.md §14): tp > 1 costs the op as the per-shard
    # instance a TP shard_map body would run — row-parallel ops (those
    # paying a boundary collective) split K, everything else splits N.
    # ``collective`` names the boundary collective this op's block pays
    # ("all-reduce" / "reduce-scatter" / "all-gather"; "" = none, the
    # column-parallel mid-block default).
    tp: int = 1
    collective: str = ""


@dataclasses.dataclass(frozen=True)
class Route:
    """One registry entry: a named way to execute a domain's op."""
    name: str
    domain: str
    priority: int                             # tie-break (lower wins)
    guard: Callable[[OpSpec], str]            # "" = applicable, else reason
    cost: Callable[[OpSpec], Tuple[float, float]]   # (flops, bytes)
    defer: Optional[Callable[[OpSpec], bool]] = None  # soft demotion (auto only)
    describe: str = ""
    # weight-stream bytes this route is costed at (the compressed-traffic
    # column of explain tables); None = not a weight-streaming route
    wbytes: Optional[Callable[[OpSpec], float]] = None


@dataclasses.dataclass
class RouteDecision:
    """One row of the explain table."""
    name: str
    applicable: bool
    reason: str                  # why not applicable ("" if it is)
    flops: float
    bytes: float
    compute_s: float
    memory_s: float
    cost_s: float
    priority: int
    deferred: bool = False
    chosen: bool = False
    forced: bool = False
    weight_bytes: float = 0.0    # weight-stream traffic term (0 = n/a)
    # TP terms (0 / tp=1 outside a sharded costing, DESIGN.md §14)
    collective_bytes: float = 0.0
    collective_s: float = 0.0
    tp: int = 1
    mesh: str = ""               # mesh shape the table was costed for

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


_REGISTRY: Dict[str, Dict[str, Route]] = {d: {} for d in DOMAINS}


def register_route(route: Route) -> Route:
    _REGISTRY[route.domain][route.name] = route
    return route


def routes_for(domain: str) -> Dict[str, Route]:
    return dict(_REGISTRY[domain])


# ---------------------------------------------------------------------------
# route-family predicates (shared with models/common + models/attention)
# ---------------------------------------------------------------------------

def pallas_route_active(cfg) -> bool:
    """The fused Pallas route family: ``gemm_impl == "pallas"`` and either
    no live device mesh, or tracing inside a TP shard_map body (where
    every operand is the per-shard local array, so the kernels apply
    unchanged — DESIGN.md §14). A *global* GSPMD graph under a live mesh
    still keeps XLA: the kernels themselves are not GSPMD-partitionable;
    the serve engine re-enters them per-shard via `shard_tp_ctx`."""
    if cfg is None or cfg.gemm_impl != "pallas":
        return False
    from repro.dist.mesh_ctx import current_mesh, shard_tp
    return current_mesh() is None or shard_tp() > 0


def flash_backend_active(cfg) -> bool:
    """Whether the fused flash-attention kernel is the selected backend:
    explicit ``attn_impl="flash"``, or "auto" with the Pallas route
    active — the same single-device-or-per-shard predicate the GEMM
    kernels use (`pallas_route_active`)."""
    if cfg.attn_impl == "flash":
        from repro.dist.mesh_ctx import current_mesh, shard_tp
        return current_mesh() is None or shard_tp() > 0
    return cfg.attn_impl == "auto" and pallas_route_active(cfg)


# ---------------------------------------------------------------------------
# overrides: env var + ModelConfig.kernel_routes
# ---------------------------------------------------------------------------

def routes_from_cfg(cfg) -> Dict[str, str]:
    """``ModelConfig.kernel_routes`` ((domain, route) pairs — tuple-of-pairs
    so the frozen config stays hashable) as a dict."""
    if cfg is None or not getattr(cfg, "kernel_routes", ()):
        return {}
    return dict(cfg.kernel_routes)


def forced_route(domain: str, cfg_routes: Optional[Dict[str, str]] = None
                 ) -> Optional[str]:
    """Resolve the override for a domain. Precedence: ``REPRO_FORCE_ROUTE``
    env var > ``ModelConfig.kernel_routes`` > None (auto). The env var is
    either one bare route name (applied to whichever domain owns it) or a
    comma list of ``domain=route`` pairs. Read at trace time — inside a
    jit the value seen at first trace sticks for that compiled shape."""
    env = os.environ.get(FORCE_ROUTE_ENV, "").strip()
    if env:
        if "=" in env:
            for pair in env.split(","):
                d, _, r = pair.partition("=")
                if d.strip() == domain and r.strip():
                    return r.strip()
        elif env in _REGISTRY[domain]:
            return env
        elif not any(env in table for table in _REGISTRY.values()):
            # bare name matching NO domain is a typo, not a different
            # domain's route — surface it once instead of silently
            # measuring auto dispatch as if it were forced
            key = ("*", env)
            if key not in _warned_forced:
                _warned_forced.add(key)
                warnings.warn(
                    f"{FORCE_ROUTE_ENV}={env!r} names no registered route "
                    f"in any domain — ignoring the override", stacklevel=2)
    if cfg_routes:
        return cfg_routes.get(domain)
    return None


# ---------------------------------------------------------------------------
# selection core
# ---------------------------------------------------------------------------

def _collective_term(spec: OpSpec, hw: Hardware) -> Tuple[float, float]:
    """Boundary-collective cost of a TP-sharded op instance (0 for tp=1 /
    no declared collective). Counted bytes are the op's [M, N] output
    payload against the ICI collective bandwidth model in
    `roofline.analysis` — the same accounting `roofline_terms` applies to
    HLO collective ops, so explain tables and dry-run rooflines agree."""
    if spec.tp <= 1 or not spec.collective:
        return 0.0, 0.0
    payload = float(spec.m) * spec.n * spec.out_itemsize
    return payload, payload / collective_bw(spec.collective, hw)


def _decide(route: Route, spec: OpSpec, hw: Hardware) -> RouteDecision:
    reason = route.guard(spec)
    flops, nbytes = route.cost(spec)
    compute_s = flops / hw.peak_flops
    memory_s = nbytes / hw.hbm_bw
    # the collective term is route-independent (inside a shard every route
    # pays the same boundary psum); it is charged as a third pipe under
    # max() because the serve path issues it while the epilogue stores
    # (overlapped collectives, DESIGN.md §14) — the slowest pipe bounds.
    coll_b, coll_s = _collective_term(spec, hw)
    return RouteDecision(
        name=route.name, applicable=(reason == ""), reason=reason,
        flops=flops, bytes=nbytes, compute_s=compute_s, memory_s=memory_s,
        cost_s=max(compute_s, memory_s, coll_s), priority=route.priority,
        deferred=bool(route.defer and route.defer(spec)),
        collective_bytes=coll_b, collective_s=coll_s, tp=spec.tp,
        weight_bytes=float(route.wbytes(spec)) if route.wbytes else 0.0)


_warned_forced: set = set()

# (domain, route) picks of the front doors, while `record_routes` is open
_ROUTE_LOG: contextvars.ContextVar[Optional[set]] = contextvars.ContextVar(
    "repro_route_log", default=None)


@contextlib.contextmanager
def record_routes():
    """Collect the ``(domain, route)`` pairs the front doors pick inside
    the block. Picks happen at trace time, so a step whose compiled
    program is already cached adds none."""
    log: set = set()
    token = _ROUTE_LOG.set(log)
    try:
        yield log
    finally:
        _ROUTE_LOG.reset(token)


def _pick(spec: OpSpec, cfg_routes: Optional[Dict[str, str]] = None) -> str:
    """`select` for a real call site: the chosen name, logged for
    `record_routes`."""
    name, _ = select(spec, cfg_routes)
    log = _ROUTE_LOG.get()
    if log is not None:
        log.add((spec.domain, name))
    return name


def select(spec: OpSpec, cfg_routes: Optional[Dict[str, str]] = None,
           hw: Hardware = HW_V5E) -> Tuple[str, List[RouteDecision]]:
    """Pick a route for ``spec``. Returns (route_name, ranked decisions).

    Forced routes (env / config) win when their guard passes; a rejected
    force warns once per (domain, route) and falls back to auto. Auto:
    lowest modeled cost among applicable, non-deferred routes, with
    priority breaking ties inside ``COST_TIE_RTOL``.
    """
    table = _REGISTRY[spec.domain]
    decisions = [_decide(r, spec, hw) for r in table.values()]
    by_name = {d.name: d for d in decisions}

    forced = forced_route(spec.domain, cfg_routes)
    chosen: Optional[str] = None
    if forced is not None:
        dec = by_name.get(forced)
        if dec is None or not dec.applicable:
            key = (spec.domain, forced)
            if key not in _warned_forced:
                _warned_forced.add(key)
                why = dec.reason if dec else "unknown route"
                warnings.warn(
                    f"forced route {forced!r} for domain {spec.domain!r} "
                    f"not applicable ({why}) — falling back to auto "
                    f"dispatch", stacklevel=2)
        else:
            dec.forced = True
            chosen = forced

    if chosen is None:
        cands = [d for d in decisions if d.applicable and not d.deferred]
        if not cands:
            cands = [d for d in decisions if d.applicable]
        assert cands, f"no applicable route in domain {spec.domain}"
        best_cost = min(d.cost_s for d in cands)
        tied = [d for d in cands
                if d.cost_s <= best_cost * (1.0 + COST_TIE_RTOL)]
        chosen = min(tied, key=lambda d: (d.priority, d.cost_s, d.name)).name

    by_name[chosen].chosen = True
    decisions.sort(key=lambda d: (not d.chosen, not d.applicable,
                                  d.cost_s, d.priority))
    return chosen, decisions


def explain(domain: str = "matmul", *, m: int, k: int, n: int,
            dtype=jnp.float32, packed: bool = False, cfg=None,
            pallas: Optional[bool] = None, hw: Hardware = HW_V5E,
            tp: Optional[int] = None, collective: str = "",
            **spec_kw) -> List[RouteDecision]:
    """Ranked route table for a hypothetical op — the introspection hook
    for tests, benchmarks and serve logs. ``pallas=None`` derives the
    route-family flag from ``cfg`` (False without one).

    ``tp=None`` derives the model-axis size from the live mesh (1 without
    one, and 1 inside a shard_map body — there the dims you pass are
    already per-shard local). With ``tp > 1`` the given dims are GLOBAL
    and the table costs the per-shard instance the TP serving path would
    run (row-parallel split of K when ``collective`` names a boundary
    collective, column split of N otherwise), with the collective-bytes
    term shown per route; the table header names the mesh it costed for.

    Pass ``epilogue_ops`` (count of bias/scale/act the real call fuses)
    when describing an actual dispatch — near the 10% tie window the
    unfused-epilogue HBM round-trips charged to the xla route can decide
    the winner, and a table built with a different epilogue than the call
    it describes can name a route the run never takes."""
    from repro.dist.mesh_ctx import current_mesh, shard_tp
    mesh = current_mesh()
    mesh_desc = ""
    if tp is None:
        tp = 1
        if shard_tp() > 0:
            mesh_desc = f"shard_map body (tp={shard_tp()}, local dims)"
        elif (mesh is not None and "model" in mesh.axis_names
                and (cfg is None or cfg.parallel != "dp")):
            tp = int(mesh.shape["model"])
    if tp > 1 and not mesh_desc:
        mesh_desc = (str(dict(mesh.shape)) if mesh is not None
                     else f"(model={tp})")
    if pallas is None:
        pallas = pallas_route_active(cfg)
        if not pallas and tp > 1 and cfg is not None \
                and cfg.gemm_impl == "pallas":
            # costing the per-shard instance: inside the shard_map body
            # the route family re-activates even though it is off in the
            # enclosing global graph
            pallas = True
    itemsize = jnp.dtype(dtype).itemsize
    spec_kw.setdefault("out_itemsize", itemsize)
    if domain in ("attention", "attn_decode", "head_sample"):
        # the attention + sampling kernels take floats only; the GEMM/conv
        # kernels also accept int8 — mirror the front doors' own float_ok
        # exactly or explain() would report routes the runtime never takes
        spec_kw.setdefault("float_ok",
                           jnp.issubdtype(jnp.dtype(dtype), jnp.floating))
    else:
        spec_kw.setdefault("float_ok",
                           jnp.issubdtype(jnp.dtype(dtype), jnp.floating)
                           or jnp.dtype(dtype) == jnp.int8)
    if domain in ("attention", "attn_decode"):
        fa = flash_backend_active(cfg) if cfg is not None else bool(pallas)
        if not fa and tp > 1 and cfg is not None and (
                cfg.attn_impl == "flash"
                or (cfg.attn_impl == "auto" and cfg.gemm_impl == "pallas")):
            fa = True           # per-shard instance re-activates flash too
        spec_kw.setdefault("flash_active", fa)
    if domain == "attention":
        spec_kw.setdefault("chunk", cfg.attn_chunk if cfg is not None
                           else 1024)
    spec = OpSpec(domain=domain, m=m, k=k, n=n, itemsize=itemsize,
                  packed=packed, pallas=bool(pallas), tp=int(tp),
                  collective=collective, **spec_kw)
    _, decisions = select(spec, routes_from_cfg(cfg), hw=hw)
    for d in decisions:
        d.mesh = mesh_desc
    return decisions


def format_table(decisions: List[RouteDecision]) -> str:
    """Compact fixed-width rendering of an explain() table for logs."""
    lines = []
    if decisions and (decisions[0].mesh or decisions[0].tp > 1):
        lines.append(f"costed for mesh {decisions[0].mesh or '?'} "
                     f"(model-axis tp={decisions[0].tp})")
    lines.append(f"{'route':<18} {'ok':<3} {'cost':>10} {'flops':>10} "
                 f"{'bytes':>10} {'wbytes':>9} {'coll':>9}  note")
    for d in decisions:
        mark = "*" if d.chosen else ("f" if d.forced else "")
        note = d.reason if not d.applicable else (
            "deferred" if d.deferred and not d.chosen else "")
        wb = f"{d.weight_bytes:>9.3g}" if d.weight_bytes else f"{'-':>9}"
        lines.append(
            f"{d.name:<18} {('y' + mark) if d.applicable else 'n':<3} "
            f"{d.cost_s * 1e6:>9.2f}u {d.flops:>10.3g} {d.bytes:>10.3g} "
            f"{wb} {d.collective_bytes:>9.3g}  {note}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# matmul domain
# ---------------------------------------------------------------------------

def _shard_dims(spec: OpSpec) -> Tuple[int, int, int]:
    """Per-shard local (m, k, n) of a TP-sharded GEMM (DESIGN.md §14):
    row-parallel ops (those declaring a reduction-boundary collective)
    split the contraction K across shards; everything else takes the
    column-parallel default and splits N. tp=1 passes dims through."""
    if spec.tp <= 1:
        return spec.m, spec.k, spec.n
    if spec.collective in ("all-reduce", "reduce-scatter"):
        return spec.m, max(spec.k // spec.tp, 1), spec.n
    return spec.m, spec.k, max(spec.n // spec.tp, 1)


def _mm_dims(spec: OpSpec, skinny: bool) -> Tuple[int, int, int]:
    """Padded (mp, kp, np) of the per-shard instance, mirroring the ops
    wrappers' block policy: the M-tiled kernels clamp bm to round_up(m, 8)
    below 128 (so small-M pads only to the sublane quantum), skinny pads
    M straight to the sublane."""
    m, k, n = _shard_dims(spec)
    if skinny:
        mp = round_up(max(m, 1), 8)
    else:
        bm = min(128, round_up(max(m, 1), 8))
        mp = round_up(max(m, 1), bm)
    return mp, round_up(max(k, 1), 128), round_up(max(n, 1), 128)


def _dense_w_bytes(spec: OpSpec, kp: int, np_: int) -> float:
    return kp * np_ * spec.itemsize


def _packed_w_bytes(spec: OpSpec) -> float:
    """Compressed weight stream: values + bitmask, the paper's 62.5%
    (the per-shard plane slice when the spec is TP-sharded). ``bits=4``
    halves the values term (two slots per byte) and adds the groupwise
    f32 scale plane — 37.5% of dense INT8 at B=8/k=4/G=128 (§16)."""
    _, k, n = _shard_dims(spec)
    nb = max(k // max(spec.block, 1), 1)
    if spec.bits == 4 and spec.group > 0:
        return (nb * spec.nnz * n * 0.5 + nb * n * _MASK_BYTES
                + max(k // spec.group, 1) * n * 4.0)
    return (nb * spec.nnz * n * spec.vals_itemsize
            + nb * n * _MASK_BYTES)


def _mm_xla_cost(spec: OpSpec) -> Tuple[float, float]:
    # per-shard dims for tp > 1: GSPMD shards the XLA matmul the same way
    # the shard_map body shards the kernels, so both route families are
    # costed at local shapes and the comparison stays honest on meshes
    m, k, n = _shard_dims(spec)
    flops = 2.0 * m * k * n
    nbytes = (m * k * spec.itemsize + m * n * spec.out_itemsize)
    if spec.packed:
        # decompress_xla: read compressed, write dense, matmul reads dense
        nbytes += _packed_w_bytes(spec) + 2 * k * n * spec.itemsize
    else:
        nbytes += k * n * spec.itemsize
    # every unfused epilogue op re-reads + re-writes the [M, N] output
    nbytes += 2.0 * m * n * spec.out_itemsize * spec.epilogue_ops
    return flops, nbytes


def _mm_kernel_cost(spec: OpSpec, *, skinny: bool, dbb: bool
                    ) -> Tuple[float, float]:
    mp, kp, np_ = _mm_dims(spec, skinny)
    flops = 2.0 * mp * kp * np_
    w = _packed_w_bytes(spec) if dbb else _dense_w_bytes(spec, kp, np_)
    nbytes = (mp * kp * spec.itemsize + w + mp * np_ * spec.out_itemsize)
    return flops, nbytes


def _tp_split_reason(spec: OpSpec) -> str:
    """Divisibility of the declared TP split (empty = clean). Row-parallel
    ops split K, column-parallel split N; a dim that doesn't divide tp
    has no per-shard kernel instance."""
    if spec.tp <= 1:
        return ""
    if spec.collective in ("all-reduce", "reduce-scatter"):
        if spec.k % spec.tp:
            return (f"unsupported axis split: K={spec.k} % tp={spec.tp} "
                    "!= 0 (row-parallel shard)")
    elif spec.n % spec.tp:
        return f"unsupported axis split: N={spec.n} % tp={spec.tp} != 0"
    return ""


def _guard_pallas_dense(spec: OpSpec) -> str:
    if spec.packed:
        return "weight is DBB-packed (dense STA kernel takes dense [K,N])"
    if not spec.pallas:
        return ("Pallas route not selected (gemm_impl != 'pallas', or a "
                "global GSPMD graph — per-shard shard_map bodies "
                "re-enable it)")
    if not spec.dense_fused:
        return "call site keeps dense weights on XLA (shardable/diff path)"
    if not spec.float_ok:
        return "operand dtype outside the kernel contract (f32/bf16/int8)"
    return _tp_split_reason(spec)


def _guard_sta(spec: OpSpec) -> str:
    r = _guard_pallas_dense(spec)
    if r:
        return r
    if spec.gemv:
        return "head GEMV: M-tiled padding gains nothing on [B,d]·[d,V]"
    return ""


def _guard_skinny_sta(spec: OpSpec) -> str:
    r = _guard_pallas_dense(spec)
    if r:
        return r
    if spec.pinned:
        return "caller-pinned block shapes opt out of skinny dispatch"
    _, k_loc, _ = _shard_dims(spec)
    if not skinny_ok(spec.m, k_loc, spec.itemsize):
        shard = "per-shard " if spec.tp > 1 else ""
        return (f"outside the skinny regime (M ≤ {SKINNY_M_MAX} and "
                f"{shard}resident [M,K] ≤ VMEM/4)")
    return ""


def _guard_packed_base(spec: OpSpec) -> str:
    """Shared admission for every packed-weight kernel route (both value-
    plane widths): format present, route family on, block divisibility,
    clean TP split."""
    if not spec.packed:
        return "weight is dense (DBB kernels take values+bitmask)"
    if not spec.pallas:
        return ("Pallas route not selected (gemm_impl != 'pallas', or a "
                "global GSPMD graph — per-shard shard_map bodies "
                "re-enable it)")
    if spec.k % max(spec.block, 1) != 0:
        return f"K={spec.k} not divisible by the DBB block {spec.block}"
    r = _tp_split_reason(spec)
    if r:
        return r
    _, k_loc, _ = _shard_dims(spec)
    if k_loc % max(spec.block, 1) != 0:
        return (f"per-shard K={k_loc} not divisible by the DBB block "
                f"{spec.block} (tp={spec.tp} splits inside a block)")
    return ""


def _guard_pallas_packed(spec: OpSpec) -> str:
    r = _guard_packed_base(spec)
    if r:
        return r
    if spec.bits == 4:
        return ("values plane is nibble-packed INT4 (the w4 routes "
                "stream it)")
    return ""


def _skinny_reason(spec: OpSpec) -> str:
    if spec.pinned:
        return "caller-pinned block shapes opt out of skinny dispatch"
    _, k_loc, _ = _shard_dims(spec)
    if not skinny_ok(spec.m, k_loc, spec.itemsize):
        shard = "per-shard " if spec.tp > 1 else ""
        return (f"outside the skinny regime (M ≤ {SKINNY_M_MAX} and "
                f"{shard}resident [M,K] ≤ VMEM/4)")
    return ""


def _guard_skinny_dbb(spec: OpSpec) -> str:
    return _guard_pallas_packed(spec) or _skinny_reason(spec)


def _guard_pallas_packed_w4(spec: OpSpec) -> str:
    r = _guard_packed_base(spec)
    if r:
        return r
    if spec.bits != 4:
        return "values plane is INT8 (w4 routes take the nibble plane)"
    if spec.itemsize == 1:
        return ("int8 activations: the w4 dequantized tile is float "
                "(float x only)")
    if spec.group <= 0 or spec.group % max(spec.block, 1) != 0:
        return (f"scale group {spec.group} must be a positive multiple "
                f"of the DBB block {spec.block}")
    _, k_loc, _ = _shard_dims(spec)
    if k_loc % spec.group != 0:
        shard = "per-shard " if spec.tp > 1 else ""
        return (f"{shard}K={k_loc} not divisible by the scale group "
                f"{spec.group}")
    return ""


def _guard_skinny_dbb_w4(spec: OpSpec) -> str:
    return _guard_pallas_packed_w4(spec) or _skinny_reason(spec)


def _xla_w_bytes(spec: OpSpec) -> float:
    _, k, n = _shard_dims(spec)
    if spec.packed:
        # decompress_xla: read compressed, write + re-read dense
        return _packed_w_bytes(spec) + 2.0 * k * n * spec.itemsize
    return float(k) * n * spec.itemsize


register_route(Route(
    name="xla", domain="matmul", priority=9,
    guard=lambda s: "",
    cost=_mm_xla_cost,
    wbytes=_xla_w_bytes,
    describe="plain XLA matmul (GSPMD-shardable, differentiable); packed "
             "weights decompress transiently in-graph"))

register_route(Route(
    name="sta", domain="matmul", priority=1,
    guard=_guard_sta,
    cost=lambda s: _mm_kernel_cost(s, skinny=False, dbb=False),
    wbytes=lambda s: _dense_w_bytes(s, *_mm_dims(s, False)[1:]),
    describe="M-tiled dense STA Pallas kernel, fused epilogue"))

register_route(Route(
    name="skinny_sta", domain="matmul", priority=0,
    guard=_guard_skinny_sta,
    cost=lambda s: _mm_kernel_cost(s, skinny=True, dbb=False),
    wbytes=lambda s: _dense_w_bytes(s, *_mm_dims(s, True)[1:]),
    describe="skinny weight-streaming STA kernel (resident [M,K] rows)"))

register_route(Route(
    name="dbb_packed", domain="matmul", priority=1,
    guard=_guard_pallas_packed,
    cost=lambda s: _mm_kernel_cost(s, skinny=False, dbb=True),
    wbytes=_packed_w_bytes,
    describe="M-tiled DBB kernel: compressed weight stream, VMEM "
             "decompress, scale folded into the epilogue"))

register_route(Route(
    name="skinny_dbb", domain="matmul", priority=0,
    guard=_guard_skinny_dbb,
    cost=lambda s: _mm_kernel_cost(s, skinny=True, dbb=True),
    wbytes=_packed_w_bytes,
    describe="skinny DBB kernel: resident rows, compressed stream"))

register_route(Route(
    name="dbb_packed_w4", domain="matmul", priority=1,
    guard=_guard_pallas_packed_w4,
    cost=lambda s: _mm_kernel_cost(s, skinny=False, dbb=True),
    wbytes=_packed_w_bytes,
    describe="M-tiled DBB kernel, nibble-packed INT4 stream (~half the "
             "weight bytes) + groupwise dequant in VMEM (§16)"))

register_route(Route(
    name="skinny_dbb_w4", domain="matmul", priority=0,
    guard=_guard_skinny_dbb_w4,
    cost=lambda s: _mm_kernel_cost(s, skinny=True, dbb=True),
    wbytes=_packed_w_bytes,
    describe="skinny DBB kernel, INT4 nibble stream + groupwise dequant "
             "— the decode weight-bandwidth floor (§16)"))


def _epilogue_ops(bias, scale, act: str) -> int:
    return int(bias is not None) + int(scale is not None) + int(act != "none")


def matmul(x: jax.Array, w, bias=None, scale=None, *, act: str = "none",
           out_dtype=None, cfg=None, pallas: Optional[bool] = None,
           dense_fused: bool = True, gemv: bool = False,
           route: Optional[str] = None, use_kernel: bool = True,
           block_m: int = 0, block_k: int = 0, block_n: int = 0
           ) -> jax.Array:
    """The one front door for every model-layer GEMM:
    ``act(scale * (x @ w) + bias)`` where ``w`` is a dense ``[K, N]`` array
    or a packed `DbbWeight`, routed through the registry.

    cfg:          supplies ``gemm_impl`` (route family), ``kernel_routes``
                  overrides, and nothing else.
    pallas:       explicit route-family flag for callers without a config
                  (`dbb_linear_apply(impl=...)`); None derives from cfg.
    dense_fused:  whether this call site opts dense weights into the fused
                  Pallas kernels (attention projections keep False — their
                  dense path stays on the shardable/differentiable XLA
                  matmul, DESIGN.md §11).
    gemv:         decode head-GEMV hint: stream through the skinny kernel
                  or stay on XLA; never pad into M tiles.
    route:        explicit route name (wins over env/config overrides —
                  the benchmark/test forcing hook).
    use_kernel=False short-circuits to the XLA route (oracle fallbacks).
    """
    packed = isinstance(w, DbbWeight)
    if pallas is None:
        pallas = pallas_route_active(cfg)
    *batch, k_dim = x.shape
    m = math.prod(batch) if batch else 1
    if packed:
        k_w, n = w.k_dim, w.values.shape[-1]
        if k_w != k_dim:
            # Inside a TP shard_map body the packed planes arrive as
            # per-shard local slices but the static aux ``k_dim`` still
            # holds the global contraction (shard_map shards arrays, not
            # static fields). The row-parallel layout splits whole
            # K-blocks across shards, so the local bitmask rebuilds it.
            k_local = w.bitmask.shape[-2] * w.block
            if k_local == k_dim:
                w = dataclasses.replace(w, k_dim=k_local)
                k_w = k_local
        vals_itemsize = jnp.dtype(w.values.dtype).itemsize
        block, nnz = w.block, w.nnz
        bits, group = w.bits, w.group
    else:
        k_w, n = w.shape
        vals_itemsize, block, nnz = 1, 8, 4
        bits, group = 8, 0
    assert k_dim == k_w, (x.shape, k_w)
    eff_out = jnp.dtype(out_dtype).itemsize if out_dtype is not None \
        else x.dtype.itemsize
    spec = OpSpec(
        domain="matmul", m=m, k=k_dim, n=n,
        itemsize=x.dtype.itemsize, out_itemsize=eff_out,
        packed=packed, block=block, nnz=nnz, vals_itemsize=vals_itemsize,
        bits=bits, group=group,
        epilogue_ops=_epilogue_ops(bias, scale if not packed else None, act),
        pallas=bool(pallas) and use_kernel, dense_fused=dense_fused,
        pinned=bool(block_m or block_k or block_n), gemv=gemv,
        float_ok=(jnp.issubdtype(x.dtype, jnp.floating)
                  or x.dtype == jnp.int8))
    if route is not None:
        dec = _decide(_REGISTRY["matmul"][route], spec, HW_V5E)
        if not dec.applicable:
            raise ValueError(f"route {route!r} rejected this op: "
                             f"{dec.reason}")
        name = route
    else:
        name = _pick(spec, routes_from_cfg(cfg))

    kw = dict(block_m=block_m, block_k=block_k, block_n=block_n)
    if name in ("sta", "skinny_sta"):
        from repro.kernels.sta_gemm.ops import sta_gemm
        return sta_gemm(x, w.astype(x.dtype), bias, scale, act=act,
                        out_dtype=out_dtype, skinny=(name == "skinny_sta"),
                        **kw)
    if name in ("dbb_packed", "skinny_dbb", "dbb_packed_w4",
                "skinny_dbb_w4"):
        from repro.kernels.dbb_gemm.ops import dbb_gemm_packed
        if scale is not None:
            # fold a caller-supplied scale into the packed weight's
            # epilogue scale — dbb_gemm_packed consumes only w.scale, and
            # dropping the operand here would silently diverge from the
            # xla route (scales are multiplicative, so folding is exact;
            # on w4 leaves the [K//G, N] plane broadcasts against [N])
            s = jnp.asarray(scale, jnp.float32)
            w = dataclasses.replace(
                w, scale=s if w.scale is None else w.scale * s)
        return dbb_gemm_packed(
            x, w, bias, act=act, out_dtype=out_dtype,
            skinny=(name in ("skinny_dbb", "skinny_dbb_w4")), **kw)
    return _matmul_xla(x, w, bias, scale, act=act, out_dtype=out_dtype)


def _matmul_xla(x, w, bias, scale, *, act, out_dtype):
    """The XLA route, numerically identical to the pre-dispatch model-layer
    fallbacks: float operands keep the legacy storage-dtype bias add; int8
    operands run the kernels' exact epilogue (int32 accumulate → f32
    scale/bias → round/clip) so forced-route parity holds bit-for-bit."""
    import dataclasses as _dc

    from repro.kernels.epilogue import Epilogue, apply_act, apply_epilogue
    if isinstance(w, DbbWeight):
        from repro.core.dbb_linear import decompress_xla
        if w.bits == 4:
            # w4: the [K//G, N] scales vary along K, so there is no int8
            # epilogue folding — dequantize fully (f32); int8 activations
            # upcast (no int8×w4 requant datapath exists anywhere)
            w = decompress_xla(w)
            if x.dtype == jnp.int8:
                x = x.astype(w.dtype)
        elif x.dtype == jnp.int8 and w.scale is not None:
            # INT8 deployment: the quant scale must survive to the int32
            # epilogue — decompress_xla(dtype=int8) would dequantize to
            # f32 and truncate back to int8, destroying the weights.
            # Decompress the raw int8 values and fold the scale into the
            # epilogue operand instead (the DBB kernels' exact datapath).
            scale = (w.scale if scale is None
                     else jnp.asarray(scale, jnp.float32) * w.scale)
            w = decompress_xla(_dc.replace(w, scale=None))
        else:
            w = decompress_xla(w, dtype=x.dtype)    # scale already applied
    if x.dtype == jnp.int8:
        acc = jnp.matmul(x, w.astype(jnp.int8),
                         preferred_element_type=jnp.int32)
        spec = Epilogue(act=act, has_bias=bias is not None,
                        has_scale=scale is not None)
        from repro.kernels.epilogue import default_out_dtype
        od = out_dtype if out_dtype is not None else default_out_dtype(
            x.dtype, spec)
        return apply_epilogue(acc, spec, od, bias=bias, scale=scale)
    y = x @ w.astype(x.dtype)
    if scale is not None:
        y = (y.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)
             ).astype(y.dtype)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    y = apply_act(y, act)
    return y.astype(out_dtype) if out_dtype is not None else y


# ---------------------------------------------------------------------------
# conv domain (implicit-GEMM convolution, DESIGN.md §8)
# ---------------------------------------------------------------------------

def _conv_padded_geom(spec: OpSpec) -> Tuple[int, int, int, int, int]:
    b, h, w_dim, c, kh, kw, stride = spec.conv_geom[:7]
    pad = spec.conv_geom[7] if len(spec.conv_geom) > 7 else "SAME"
    from repro.kernels.conv_gemm.ops import _default_tiles, out_spatial
    ho, _, _ = out_spatial(h, kh, stride, pad)
    wo, _, _ = out_spatial(w_dim, kw, stride, pad)
    th, _ = _default_tiles(ho, wo)
    hp = (round_up(max(ho, 1), th) - 1) * stride + kh
    wp = (wo - 1) * stride + kw
    return ho, wo, th, hp, wp


def _conv_kernel_cost(spec: OpSpec, dbb: bool) -> Tuple[float, float]:
    kp, np_ = round_up(spec.k, 128), round_up(spec.n, 128)
    flops = 2.0 * spec.m * kp * np_
    w_bytes = _packed_w_bytes(spec) if dbb else kp * np_ * spec.itemsize
    if len(spec.conv_geom) < 7:
        # geometry-free spec (explain() without conv_geom): approximate
        # the resident-image term with the implied GEMM's activation
        # reads; the guard already marks these routes inapplicable
        img_bytes = float(spec.m) * spec.k * spec.itemsize
    else:
        b, _, _, c = spec.conv_geom[:4]
        _, _, _, hp, wp = _conv_padded_geom(spec)
        img_bytes = b * hp * wp * c * spec.itemsize  # resident image blocks
    nbytes = img_bytes + w_bytes + spec.m * np_ * spec.out_itemsize
    return flops, nbytes


def _conv_xla_cost(spec: OpSpec) -> Tuple[float, float]:
    flops = 2.0 * spec.m * spec.k * spec.n
    w_bytes = (_packed_w_bytes(spec) + spec.k * spec.n * spec.itemsize
               if spec.packed else spec.k * spec.n * spec.itemsize)
    # the explicit path writes AND re-reads the materialized [M, K] im2col
    nbytes = (spec.m * spec.k * spec.itemsize       # image gather reads
              + 2.0 * spec.m * spec.k * spec.itemsize
              + w_bytes + spec.m * spec.n * spec.out_itemsize
              + 2.0 * spec.m * spec.n * spec.out_itemsize
              * spec.epilogue_ops)
    return flops, nbytes


def _conv_vmem_ok(spec: OpSpec, dbb: bool) -> bool:
    from repro.kernels.conv_gemm.ops import _vmem_fits
    _, wo, th, hp, wp = _conv_padded_geom(spec)
    c, kw = spec.conv_geom[3], spec.conv_geom[5]
    return _vmem_fits(hp, wp, c, kw, th, wo, 128, spec.itemsize, dbb)


def _guard_conv_sta(spec: OpSpec) -> str:
    if spec.packed:
        return "weight is DBB-packed"
    if not spec.pallas:
        return "implicit-GEMM kernels not selected (use_kernel=False)"
    if len(spec.conv_geom) < 7:
        return ("conv_geom=(b, h, w, c, kh, kw, stride[, padding]) "
                "required (the VMEM guard needs the image geometry)")
    if not _conv_vmem_ok(spec, dbb=False):
        return "resident image block exceeds the VMEM budget"
    return ""


def _guard_conv_dbb(spec: OpSpec) -> str:
    if not spec.packed:
        return "weight is dense"
    if spec.bits == 4:
        return ("conv kernels stream the INT8 DBB plane only (w4 is the "
                "decode GEMM format; conv decompresses it up front)")
    if not spec.pallas:
        return "implicit-GEMM kernels not selected (use_kernel=False)"
    if len(spec.conv_geom) < 7:
        return ("conv_geom=(b, h, w, c, kh, kw, stride[, padding]) "
                "required (the VMEM guard needs the image geometry)")
    c, kw = spec.conv_geom[3], spec.conv_geom[5]
    if (kw * c) % max(spec.block, 1) != 0:
        return (f"kw·C = {kw * c} not divisible by the DBB block "
                f"{spec.block} (K steps must cover whole blocks)")
    if not _conv_vmem_ok(spec, dbb=True):
        return "resident image block exceeds the VMEM budget"
    return ""


register_route(Route(
    name="conv_xla", domain="conv", priority=9,
    guard=lambda s: "",
    cost=_conv_xla_cost,
    describe="explicit im2col + GEMM oracle (materialized patch matrix)"))

register_route(Route(
    name="conv_sta", domain="conv", priority=0,
    guard=_guard_conv_sta,
    cost=lambda s: _conv_kernel_cost(s, dbb=False),
    describe="implicit-GEMM dense kernel: im2col gathered in VMEM"))

register_route(Route(
    name="conv_dbb", domain="conv", priority=0,
    guard=_guard_conv_dbb,
    cost=lambda s: _conv_kernel_cost(s, dbb=True),
    describe="implicit-GEMM DBB kernel: compressed weight stream"))


def conv(x: jax.Array, w, bias=None, *, kh: int, kw: int, stride: int = 1,
         padding: str = "SAME", act: str = "none", out_dtype=None,
         cfg=None, route: Optional[str] = None, use_kernel: bool = True,
         **tile_kw) -> jax.Array:
    """Front door for conv-as-GEMM: ``conv2d(x, w) (+bias, act)`` with
    ``w`` a dense ``[kh·kw·C, N]`` GEMM weight or a packed `DbbWeight`.
    The implied GEMM is M = B·Ho·Wo, K = kh·kw·C, N. ``use_kernel=False``
    pins the explicit im2col oracle (the conv_xla route)."""
    from repro.kernels.conv_gemm.ops import out_spatial
    packed = isinstance(w, DbbWeight)
    if packed and w.bits == 4:
        # conv kernels stream the INT8 plane only — w4 is a decode-GEMM
        # format. Decompress once (XLA) and take the dense routes rather
        # than silently mis-reading the nibble plane as int8 slots.
        from repro.core.dbb import unpack_dbb
        w = unpack_dbb(w).astype(x.dtype)
        packed = False
    b, h, w_dim, c = x.shape
    ho, _, _ = out_spatial(h, kh, stride, padding)
    wo, _, _ = out_spatial(w_dim, kw, stride, padding)
    if packed:
        n = w.values.shape[-1]
        block, nnz = w.block, w.nnz
        vals_itemsize = jnp.dtype(w.values.dtype).itemsize
    else:
        n = w.shape[1]
        block, nnz, vals_itemsize = 8, 4, 1
    spec = OpSpec(
        domain="conv", m=b * ho * wo, k=kh * kw * c, n=n,
        itemsize=x.dtype.itemsize, out_itemsize=x.dtype.itemsize,
        packed=packed, block=block, nnz=nnz, vals_itemsize=vals_itemsize,
        epilogue_ops=_epilogue_ops(bias, None, act),
        pallas=use_kernel,
        conv_geom=(b, h, w_dim, c, kh, kw, stride, padding),
        float_ok=(jnp.issubdtype(x.dtype, jnp.floating)
                  or x.dtype == jnp.int8))
    if route is not None:
        dec = _decide(_REGISTRY["conv"][route], spec, HW_V5E)
        if not dec.applicable:
            raise ValueError(f"route {route!r} rejected this op: "
                             f"{dec.reason}")
        name = route
    else:
        name = _pick(spec, routes_from_cfg(cfg))

    from repro.kernels.conv_gemm.ops import conv_gemm, conv_gemm_packed
    kernel = name != "conv_xla"
    if packed:
        return conv_gemm_packed(x, w, bias, kh=kh, kw=kw, stride=stride,
                                padding=padding, act=act,
                                out_dtype=out_dtype, use_kernel=kernel,
                                **tile_kw)
    return conv_gemm(x, w, bias, kh=kh, kw=kw, stride=stride,
                     padding=padding, act=act, out_dtype=out_dtype,
                     use_kernel=kernel, **tile_kw)


# ---------------------------------------------------------------------------
# attention domain (full-sequence core, DESIGN.md §10)
# ---------------------------------------------------------------------------

def _guard_attn_flash(spec: OpSpec) -> str:
    if spec.packed_seq:
        return "packed cu_seqlens batch (block-diagonal masking required)"
    if not spec.flash_active:
        return ("flash backend not selected (attn_impl/gemm_impl pin the "
                "XLA paths, or a global GSPMD graph — per-shard shard_map "
                "bodies re-enable it)")
    if not spec.float_ok:
        return "non-float operands"
    from repro.kernels.attn.ops import flash_ok
    if not flash_ok(spec.m, spec.n, spec.k, spec.itemsize):
        return "smallest legal (bq, bkv) block pair exceeds VMEM"
    return ""


def _guard_attn_chunked(spec: OpSpec) -> str:
    if spec.packed_seq:
        return "packed cu_seqlens batch (block-diagonal masking required)"
    if spec.ragged:
        return "ragged per-row positions (chunked masks assume one ladder)"
    if spec.m != spec.n:
        return "not a self-attention full-sequence call (T != S)"
    if spec.n % max(spec.chunk, 1) != 0:
        return f"S={spec.n} not divisible by attn_chunk={spec.chunk}"
    return ""


def _attn_cost(spec: OpSpec, score_passes: float) -> Tuple[float, float]:
    # per-row (t, s) work × the padded batch rows. Packed specs carry the
    # whole batch's token count in m with batch=1, which is exactly what
    # makes their roofline honest: total_tokens · s_visible instead of
    # B · T_max² (DESIGN.md §12)
    t, s, d, b = spec.m, spec.n, spec.k, max(spec.batch, 1)
    flops = 4.0 * b * t * s * d
    nbytes = b * ((2 * t * d + 2 * s * d) * spec.itemsize
                  + score_passes * t * s * _F32)
    return flops, nbytes


register_route(Route(
    name="attn_flash", domain="attention", priority=0,
    guard=_guard_attn_flash,
    cost=lambda s: _attn_cost(s, 0.0),
    describe="fused Pallas flash kernel: online softmax, no score tensor"))

register_route(Route(
    name="attn_chunked", domain="attention", priority=1,
    guard=_guard_attn_chunked,
    # one recomputed score-tile pass; deferred below 2 chunks where the
    # unrolled-scan overhead beats the naive path's extra score traffic
    cost=lambda s: _attn_cost(s, 1.0),
    defer=lambda s: s.n <= 2 * s.chunk,
    describe="blocked XLA path with running-softmax combine"))

register_route(Route(
    name="attn_naive", domain="attention", priority=2,
    guard=lambda s: ("packed cu_seqlens batch (block-diagonal masking "
                     "required)" if s.packed_seq else ""),
    cost=lambda s: _attn_cost(s, 2.0),
    describe="quadratic oracle (full [T,S] score bias materialized)"))


def _guard_attn_packed_flash(spec: OpSpec) -> str:
    if not spec.packed_seq:
        return "not a packed cu_seqlens batch"
    if not spec.flash_active:
        return ("flash backend not selected (attn_impl/gemm_impl pin the "
                "XLA paths, or a global GSPMD graph — per-shard shard_map "
                "bodies re-enable it)")
    if not spec.float_ok:
        return "non-float operands"
    from repro.kernels.attn.ops import flash_ok
    if not flash_ok(spec.m, spec.n, spec.k, spec.itemsize):
        return "smallest legal (bq, bkv) block pair exceeds VMEM"
    return ""


register_route(Route(
    name="attn_packed_flash", domain="attention", priority=0,
    guard=_guard_attn_packed_flash,
    cost=lambda s: _attn_cost(s, 0.0),
    describe="cu_seqlens flash kernel: block-diagonal-causal over packed "
             "total_tokens, zero pad rows"))

register_route(Route(
    name="attn_packed_ref", domain="attention", priority=3,
    guard=lambda s: ("" if s.packed_seq else "not a packed cu_seqlens "
                     "batch"),
    cost=lambda s: _attn_cost(s, 2.0),
    describe="quadratic packed oracle (full [T,T] segment-mask score "
             "tensor)"))

_ATTN_IMPL_ROUTE = {"flash": "attn_flash", "chunked": "attn_chunked",
                    "naive": "attn_naive"}
# packed calls have no chunked implementation: anything but flash drops to
# the quadratic packed oracle
_PACKED_IMPL_ROUTE = {"flash": "attn_packed_flash",
                      "chunked": "attn_packed_ref",
                      "naive": "attn_packed_ref"}
# a kernel_routes pin on a padded route carries its intent (kernel vs XLA)
# to the packed variant instead of tripping the forced-route warning
_ATTN_TO_PACKED = {"attn_flash": "attn_packed_flash",
                   "attn_chunked": "attn_packed_ref",
                   "attn_naive": "attn_packed_ref"}


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              positions: jax.Array, cfg, ragged: bool = False) -> jax.Array:
    """Front door for full-sequence attention dispatch (flash / chunked /
    naive) on projected q/k/v in model layout. Replaces the old
    `models.attention._attention_core` inline guard chain; the route
    implementations stay in `models.attention`."""
    from repro.models import attention as A
    t, s = q.shape[1], k.shape[1]
    spec = OpSpec(
        domain="attention", m=t, k=q.shape[-1], n=s,
        itemsize=q.dtype.itemsize, out_itemsize=q.dtype.itemsize,
        ragged=ragged, chunk=cfg.attn_chunk, batch=q.shape[0],
        flash_active=flash_backend_active(cfg),
        float_ok=jnp.issubdtype(q.dtype, jnp.floating))
    cfg_routes = dict(routes_from_cfg(cfg))
    # attn_impl is the config-level override for this domain (kept for
    # compatibility; kernel_routes["attention"] wins if both are set)
    if cfg.attn_impl in _ATTN_IMPL_ROUTE:
        cfg_routes.setdefault("attention", _ATTN_IMPL_ROUTE[cfg.attn_impl])
    name = _pick(spec, cfg_routes)

    if name == "attn_flash":
        from repro.kernels.attn import flash_attention
        return flash_attention(
            q, k, v, A._start_from_positions(positions, q.shape[0]),
            window=cfg.sliding_window, softcap=cfg.attn_logit_softcap)
    if ragged:          # per-row ladders: only flash and naive mask them
        return A._naive_attention(q, k, v, positions, positions, cfg)
    if name == "attn_chunked":
        return A._chunked_causal_attention(q, k, v, cfg, cfg.attn_chunk)
    pos1d = positions[0] if positions.ndim > 1 else positions
    return A._naive_attention(q, k, v, pos1d, pos1d, cfg)


# a continuation chunk is not a full-sequence call (T != S, per-row offset
# ladder): the chunked path has no implementation for it, so a chunked pin
# degrades to naive rather than warning every trace
_CHUNK_IMPL_ROUTE = {"flash": "attn_flash", "chunked": "attn_naive",
                     "naive": "attn_naive"}


def chunk_attention_route(cfg, *, t: int, s: int, d: int, itemsize: int,
                          floating: bool = True) -> str:
    """Route gate for a chunked-prefill continuation (DESIGN.md §12): T
    chunk queries at an absolute offset against one row's S cache slots.
    Flash serves it through ``q_offset``; everything else drops to the
    naive qpos/kpos mask."""
    spec = OpSpec(domain="attention", m=t, k=d, n=s, itemsize=itemsize,
                  out_itemsize=itemsize, ragged=True, chunk=cfg.attn_chunk,
                  flash_active=flash_backend_active(cfg), float_ok=floating)
    cfg_routes = dict(routes_from_cfg(cfg))
    if cfg_routes.get("attention") == "attn_chunked":
        cfg_routes["attention"] = "attn_naive"
    if cfg.attn_impl in _CHUNK_IMPL_ROUTE:
        cfg_routes.setdefault("attention", _CHUNK_IMPL_ROUTE[cfg.attn_impl])
    name = _pick(spec, cfg_routes)
    return name


def packed_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     seg_ids: jax.Array, cfg) -> jax.Array:
    """Front door for packed (cu_seqlens) prefill attention: ``q/k/v
    [1, T, H, D]`` where T is the ragged batch's TOTAL token count and
    ``seg_ids [T]`` names the owning request per packed position
    (DESIGN.md §12). The spec charges m = total_tokens with batch=1 — the
    honest roofline the padded route table can't express."""
    from repro.kernels.attn import packed_flash_attention
    t = q.shape[1]
    spec = OpSpec(
        domain="attention", m=t, k=q.shape[-1], n=t,
        itemsize=q.dtype.itemsize, out_itemsize=q.dtype.itemsize,
        packed_seq=True, chunk=cfg.attn_chunk,
        flash_active=flash_backend_active(cfg),
        float_ok=jnp.issubdtype(q.dtype, jnp.floating))
    cfg_routes = dict(routes_from_cfg(cfg))
    if cfg_routes.get("attention") in _ATTN_TO_PACKED:
        cfg_routes["attention"] = _ATTN_TO_PACKED[cfg_routes["attention"]]
    if cfg.attn_impl in _PACKED_IMPL_ROUTE:
        cfg_routes.setdefault("attention", _PACKED_IMPL_ROUTE[cfg.attn_impl])
    name = _pick(spec, cfg_routes)
    o = packed_flash_attention(
        q[0], k[0], v[0], seg_ids, window=cfg.sliding_window,
        softcap=cfg.attn_logit_softcap,
        use_kernel=(name == "attn_packed_flash"))
    return o[None]


# ---------------------------------------------------------------------------
# attn_decode domain (single-token decode against the KV cache)
# ---------------------------------------------------------------------------

def _guard_decode_flash(spec: OpSpec) -> str:
    if spec.ring:
        return "ring-buffer (sliding-window) cache layout"
    if not spec.flash_active:
        return ("flash backend not selected (attn_impl/gemm_impl pin the "
                "XLA paths, or a global GSPMD graph — per-shard shard_map "
                "bodies re-enable it)")
    if not spec.float_ok:
        return "non-float operands"
    if not skinny_ok(spec.m, spec.k, spec.itemsize):
        return (f"GQA group {spec.m} exceeds the resident-query gate "
                f"(SKINNY_M_MAX={SKINNY_M_MAX})")
    if spec.page < 8:
        return f"page {spec.page} below the 8-slot sublane quantum"
    if spec.n % max(spec.page, 1) != 0:
        return f"cache length {spec.n} not a multiple of page {spec.page}"
    from repro.kernels.attn.ops import paged_decode_ok
    if not paged_decode_ok(spec.page, spec.kv_heads, spec.k, spec.itemsize):
        return "KV page tile exceeds the decode kernel's VMEM budget"
    return ""


register_route(Route(
    name="attn_decode_flash", domain="attn_decode", priority=0,
    guard=_guard_decode_flash,
    cost=lambda s: (4.0 * s.m * s.n * s.k,
                    (s.m * s.k + 2 * s.n * s.k) * s.itemsize),
    describe="paged flash decode kernel (contiguous cache = identity "
             "block table)"))

register_route(Route(
    name="attn_decode_xla", domain="attn_decode", priority=1,
    guard=lambda s: "",
    cost=lambda s: (4.0 * s.m * s.n * s.k,
                    (s.m * s.k + 2 * s.n * s.k) * s.itemsize
                    + 2.0 * s.m * s.n * _F32),
    describe="XLA softmax decode (materialized [B,H,G,1,Smax] scores)"))


def decode_attention_route(cfg, *, group: int, head_dim: int, itemsize: int,
                           page: int, smax: int, kv_heads: int = 1,
                           ring: bool = False, floating: bool = True) -> str:
    """Route selection for one-token decode attention — the gate that used
    to live inline in `decode_attention_apply`. Returns a route name from
    the ``attn_decode`` domain."""
    spec = OpSpec(domain="attn_decode", m=group, k=head_dim, n=smax,
                  kv_heads=kv_heads, itemsize=itemsize,
                  out_itemsize=itemsize, page=page, ring=ring,
                  flash_active=flash_backend_active(cfg), float_ok=floating)
    name = _pick(spec, routes_from_cfg(cfg))
    return name


# ---------------------------------------------------------------------------
# head_sample domain (fused sampling head, DESIGN.md §15)
# ---------------------------------------------------------------------------

# VPU ops per logit in the sampling epilogue: penalty selects + 3 hash
# mixes (~4 ops each) + the log/log/scale of the gumbel transform
_SAMPLE_EPI_OPS = 16.0


def _guard_head_sample_fused(spec: OpSpec) -> str:
    if not spec.pallas:
        return ("Pallas route not selected (gemm_impl != 'pallas', or a "
                "global GSPMD graph — per-shard shard_map bodies "
                "re-enable it)")
    if not spec.float_ok:
        return "non-float hidden rows (the sampling epilogue is f32)"
    if spec.sample_tt:
        return ("top-k/top-p are global order statistics — the streaming "
                "epilogue cannot sort the row (XLA sampler materializes)")
    r = _tp_split_reason(spec)      # vocab-parallel: column split of N
    if r:
        return r
    if not skinny_ok(spec.m, spec.k, spec.itemsize):
        return (f"outside the skinny regime (M ≤ {SKINNY_M_MAX} and "
                f"resident [M,K] ≤ VMEM/4)")
    _, _, n_loc = _shard_dims(spec)
    if spec.k % 128 or n_loc % 128:
        return (f"K={spec.k} / local N={n_loc} not divisible by the "
                "128-lane tile (vocab padding could win the argmax)")
    return ""


def _hs_fused_cost(spec: OpSpec) -> Tuple[float, float]:
    mp, kp, np_ = _mm_dims(spec, skinny=True)
    flops = 2.0 * mp * kp * np_ + _SAMPLE_EPI_OPS * mp * np_
    # resident rows + streamed weight + streamed counts; the logits and
    # scores live only in VMEM — output traffic is the [M, 1] scalar pair
    nbytes = (mp * kp * spec.itemsize + kp * np_ * spec.itemsize
              + mp * np_ * _F32 + 2.0 * mp * _F32)
    return flops, nbytes


def _hs_xla_cost(spec: OpSpec) -> Tuple[float, float]:
    m, k, n = _shard_dims(spec)
    flops = 2.0 * m * k * n + _SAMPLE_EPI_OPS * m * n
    # the GEMV writes [M, N] logits to HBM, then the sampler re-reads
    # them for the penalty pass and the score/argmax pass
    nbytes = (m * k * spec.itemsize + k * n * spec.itemsize
              + m * n * _F32 + 2.0 * 2.0 * m * n * _F32
              + m * n * _F32)                       # counts read
    if spec.sample_tt:
        # sort + softmax/cumsum of the sorted row, another ~2 round-trips
        nbytes += 4.0 * m * n * _F32
    return flops, nbytes


register_route(Route(
    name="head_sample_fused", domain="head_sample", priority=0,
    guard=_guard_head_sample_fused,
    cost=_hs_fused_cost,
    describe="skinny head GEMV + fused penalty/temperature/Gumbel "
             "epilogue; logits never materialized, scalar (score, id) "
             "out (vocab-parallel combine under TP)"))

register_route(Route(
    name="head_sample_xla", domain="head_sample", priority=9,
    guard=lambda s: "",
    cost=_hs_xla_cost,
    describe="materialized [B,V] logits + XLA reference sampler "
             "(top-k/top-p capable)"))


def head_sample(h: jax.Array, w_head, counts: jax.Array, temp, rep, pres,
                freq, seed, step, *, top_k=None, top_p=None,
                use_tt: bool = False, base=0, cfg=None,
                pallas: Optional[bool] = None, route: Optional[str] = None,
                return_score: bool = False):
    """Front door for the sampling head: one token per row from hidden
    rows ``h [B, K]`` against the head weight ``w_head [K, N]``, with the
    TensorRT-LLM-contract penalties read from ``counts [B, N]`` and
    counter-hash Gumbel noise keyed by per-row ``(seed, step)``.

    ``use_tt`` is a STATIC flag — pass True only when some live row
    actually uses top-k/top-p; it forces the XLA sampler route (the
    masks are global order statistics) and traces the masking code.
    ``base`` offsets noise to global vocab ids for vocab-parallel TP
    shards; ``return_score=True`` additionally returns the winning score
    so the caller can run the scalar (max, argmax) shard combine.
    """
    b, k_dim = h.shape
    k_w, n = w_head.shape
    assert k_dim == k_w, (h.shape, w_head.shape)
    if pallas is None:
        pallas = pallas_route_active(cfg)
    spec = OpSpec(
        domain="head_sample", m=b, k=k_dim, n=n,
        itemsize=4, out_itemsize=4, gemv=True, pallas=bool(pallas),
        sample_tt=bool(use_tt),
        float_ok=jnp.issubdtype(h.dtype, jnp.floating))
    if route is not None:
        dec = _decide(_REGISTRY["head_sample"][route], spec, HW_V5E)
        if not dec.applicable:
            raise ValueError(f"route {route!r} rejected this op: "
                             f"{dec.reason}")
        name = route
    else:
        name = _pick(spec, routes_from_cfg(cfg))

    if name == "head_sample_fused":
        from repro.kernels.sample.ops import head_sample_fused
        score, tok = head_sample_fused(
            h, w_head, counts, temp, rep, pres, freq, seed, step,
            base=base)
    else:
        from repro.kernels.sample.ref import sample_argmax
        logits = matmul(h.astype(jnp.float32),
                        w_head.astype(jnp.float32), cfg=cfg,
                        pallas=bool(pallas), gemv=True)
        score, tok = sample_argmax(
            logits, counts, temp, rep, pres, freq, seed, step,
            base=base, top_k=top_k, top_p=top_p, use_tt=use_tt)
    return (score, tok) if return_score else tok
