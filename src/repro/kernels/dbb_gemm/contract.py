"""KernelContract declarations for the M-tiled DBB GEMM
(`dbb_gemm_pallas`) — DESIGN.md §13.

Same grid and accumulation discipline as the dense STA kernel; the
weight operands are the compressed stream (values ``[K/B·nnz, N]``
slot-major + bitmask ``[K/B, N]``), and the kernel body decompresses
one dense ``[bk, bn]`` tile in VMEM per K step — declared here as
``extra_vmem_bytes`` so the budget pass sees what the BlockSpecs alone
don't show.
"""
from __future__ import annotations

from typing import List

from repro.analysis.contracts import BlockDecl, KernelContract, ScratchDecl
from repro.core.sta import KERNEL_VMEM_BUDGET
from repro.kernels.common import round_up

__all__ = ["contracts"]


def _instance(m: int, k: int, n: int, *, block: int = 8, nnz: int = 4,
              itemsize: int = 4, bits: int = 8, group: int = 0
              ) -> KernelContract:
    bm, bk, bn = min(128, round_up(m, 8)), 128, 128
    mp, np_ = round_up(m, bm), round_up(n, bn)
    admitted = k % block == 0 and k % bk == 0
    if bits == 4:
        admitted = admitted and group > 0 and k % group == 0
    kp = round_up(k, bk)
    grid = (mp // bm, np_ // bn, kp // bk)
    nb_tile = bk // block
    bkc = nb_tile * nnz
    nb_total = kp // block

    inputs = [BlockDecl("x", (bm, bk), lambda i, j, kk: (i, kk), (mp, kp),
                        itemsize)]
    if bits == 4:
        inputs += [
            # nibble plane: two compressed rows per streamed byte row
            BlockDecl("values", (bkc // 2, bn), lambda i, j, kk: (kk, j),
                      (nb_total * nnz // 2, np_), 1),
            BlockDecl("bitmask", (nb_tile, bn), lambda i, j, kk: (kk, j),
                      (nb_total, np_), 4),
            # the whole [K/G, bn] scale column, rows sliced per K tile
            BlockDecl("gscale", (kp // group, bn),
                      lambda i, j, kk: (0, j), (kp // group, np_), 4),
        ]
        # expansion chain per K step (DESIGN.md §16): int32 slots (the
        # sign-extension shifts run on int32) + dense int32 tile +
        # dequantized f32 tile
        extra = 4 * (bkc * bn + bk * bn + bk * bn)
    else:
        inputs += [
            BlockDecl("values", (bkc, bn), lambda i, j, kk: (kk, j),
                      (nb_total * nnz, np_), itemsize),
            BlockDecl("bitmask", (nb_tile, bn), lambda i, j, kk: (kk, j),
                      (nb_total, np_), 4),
        ]
        extra = bk * bn * itemsize     # decompressed dense weight tile

    kind = "dbb_packed_w4" if bits == 4 else "dbb_packed"
    return KernelContract(
        name=f"dbb_gemm[m{m} k{k} n{n} B{block} z{nnz} b{bits}]",
        route=kind, domain="matmul",
        grid=grid,
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        inputs=tuple(inputs),
        outputs=(BlockDecl("out", (bm, bn), lambda i, j, kk: (i, j),
                           (mp, np_), 4),),
        scratch=(ScratchDecl("acc", (bm, bn), 4),),
        acc_dims=(2,), guarded_init=True, guarded_store=True,
        vmem_budget=KERNEL_VMEM_BUDGET,
        extra_vmem_bytes=extra,
        admitted=admitted, vmem_reject=False,
        notes="" if admitted else f"K={k} not divisible by block {block}")


def contracts() -> List[KernelContract]:
    return [
        _instance(256, 512, 512),
        _instance(64, 1024, 256),
        _instance(128, 252, 256),      # guard-rejected: K % block != 0
        # nibble-plane prefill-shaped instances (DESIGN.md §16)
        _instance(256, 1024, 512, bits=4, group=128),
        _instance(64, 512, 256, bits=4, group=256),
    ]
