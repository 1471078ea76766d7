"""KernelContract declarations for the skinny weight-streaming kernels
(`sta_gemm_skinny_pallas` / `dbb_gemm_skinny_pallas`) — DESIGN.md §13.

The decode-shaped regime: the whole padded activation block ``[mp, kp]``
is grid-constant (``resident``) while weight tiles stream over an
(N, K) grid; the output row block is revisited over the K dim. The
resident block is budgeted separately (`SKINNY_RESIDENT_BUDGET`,
VMEM/4) — exactly what `skinny_ok` enforces — and the contract set
includes both sides of that boundary so guard/constant drift in either
direction trips the vmem pass: the largest K that exactly fills the
budget (admitted) and one K tile beyond it (rejected).
"""
from __future__ import annotations

from typing import List

from repro.analysis.contracts import BlockDecl, KernelContract, ScratchDecl
from repro.core.sta import KERNEL_VMEM_BUDGET, LANE, SUBLANE
from repro.kernels.common import SKINNY_RESIDENT_BUDGET, round_up, skinny_ok

__all__ = ["contracts"]


def _instance(m: int, k: int, n: int, *, itemsize: int = 4,
              dbb: bool = False, block: int = 8, nnz: int = 4,
              bits: int = 8, group: int = 0) -> KernelContract:
    mp = round_up(max(m, 1), SUBLANE)
    kp = round_up(max(k, 1), LANE)
    np_ = round_up(max(n, 1), LANE)
    bk, bn = LANE, LANE
    grid = (np_ // bn, kp // bk)
    admitted = skinny_ok(m, k, itemsize)
    if dbb:
        admitted = admitted and k % block == 0
    if bits == 4:
        admitted = admitted and group > 0 and k % group == 0

    inputs = [BlockDecl("x", (mp, kp), lambda j, kk: (0, 0), (mp, kp),
                        itemsize, resident=True)]
    extra = 0
    if dbb:
        nb_tile = bk // block
        nb_total = kp // block
        kc_tile = nb_tile * nnz        # compressed (int8-slot) rows/tile
        if bits == 4:
            inputs += [
                # nibble plane: two compressed rows per streamed byte row
                BlockDecl("values", (kc_tile // 2, bn),
                          lambda j, kk: (kk, j),
                          (nb_total * nnz // 2, np_), 1),
                BlockDecl("bitmask", (nb_tile, bn), lambda j, kk: (kk, j),
                          (nb_total, np_), 4),
                # the whole [K/G, bn] scale column, rows sliced per tile
                BlockDecl("gscale", (kp // group, bn),
                          lambda j, kk: (0, j), (kp // group, np_), 4),
            ]
            # expansion chain per tile, all live in VMEM at the
            # decompress step: int32 slots (the sign-extension shifts run
            # on int32) + dense int32 tile + dequantized f32 tile
            # (DESIGN.md §16)
            extra = 4 * (kc_tile * bn + bk * bn + bk * bn)
        else:
            inputs += [
                BlockDecl("values", (kc_tile, bn),
                          lambda j, kk: (kk, j), (nb_total * nnz, np_),
                          itemsize),
                BlockDecl("bitmask", (nb_tile, bn), lambda j, kk: (kk, j),
                          (nb_total, np_), 4),
            ]
            extra = bk * bn * itemsize  # decompressed dense weight tile
    else:
        inputs.append(BlockDecl("w", (bk, bn), lambda j, kk: (kk, j),
                                (kp, np_), itemsize))

    kind = ("skinny_dbb_w4" if bits == 4 else
            "skinny_dbb" if dbb else "skinny_sta")
    return KernelContract(
        name=f"{kind}[m{m} k{k} n{n} i{itemsize}]",
        route=kind, domain="matmul",
        grid=grid,
        dimension_semantics=("parallel", "arbitrary"),
        inputs=tuple(inputs),
        outputs=(BlockDecl("out", (mp, bn), lambda j, kk: (0, j),
                           (mp, np_), 4),),
        scratch=(ScratchDecl("acc", (mp, bn), 4),),
        acc_dims=(1,), guarded_init=True, guarded_store=True,
        vmem_budget=KERNEL_VMEM_BUDGET,
        resident_budget=SKINNY_RESIDENT_BUDGET,
        extra_vmem_bytes=extra,
        admitted=admitted, vmem_reject=not admitted)


def contracts() -> List[KernelContract]:
    # K that exactly fills the resident budget for mp = 8, f32 — and the
    # first K one lane-tile past it (rejected by skinny_ok)
    k_fit = SKINNY_RESIDENT_BUDGET // (SUBLANE * 4)
    return [
        _instance(1, 2048, 32000),                    # decode head GEMV
        _instance(8, 256, 1024),                      # GQA group GEMM
        _instance(32, 4096, 4096),                    # skinny cap
        _instance(8, k_fit, 256),                     # boundary: fits
        _instance(8, k_fit + LANE, 256),              # boundary: rejected
        _instance(8, 256, 1024, dbb=True),
        _instance(32, 2048, 512, dbb=True),
        # nibble-plane decode stream (DESIGN.md §16): group nests inside
        # the K tile (G=128 == bk) and spans multiple tiles (G=256)
        _instance(8, 2048, 8192, dbb=True, bits=4, group=128),
        _instance(32, 1024, 512, dbb=True, bits=4, group=256),
    ]
