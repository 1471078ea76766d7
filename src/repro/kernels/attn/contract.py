"""KernelContract declarations for the attention kernels
(`flash_prefill_pallas`, `flash_prefill_packed_pallas`,
`paged_decode_pallas`) — DESIGN.md §13.

All three share the flash discipline: the output block's index map
ignores the KV grid dim (revisited once per KV block), with running
(m, l, acc) scratch guarded by first/last-visit ``pl.when``. The score
tile ``[bq, bkv]`` is a kernel-body intermediate, not a BlockSpec, so
it rides in ``extra_vmem_bytes`` — the same term `_footprint` charges.
The per-row start / q_offset scalars and the decode block table and
layer index are scalar-prefetched (SMEM), not VMEM blocks; the paged
decode contract closes its KV index maps over a concrete identity block
table and the last layer of a stacked pool, mirroring that
indirection.
"""
from __future__ import annotations

from typing import List

import numpy as np

from repro.analysis.contracts import BlockDecl, KernelContract, ScratchDecl
from repro.core.sta import KERNEL_VMEM_BUDGET, SUBLANE
from repro.kernels.attn.ops import (_heuristic_blocks, flash_ok,
                                    paged_decode_ok)
from repro.kernels.common import round_up, skinny_ok

__all__ = ["contracts"]


def _flash(b: int, hq: int, hkv: int, t: int, s: int, d: int,
           itemsize: int = 4) -> KernelContract:
    bq, bkv = _heuristic_blocks(t, s, d, itemsize)
    tp, sp = round_up(t, bq), round_up(s, bkv)
    grid = (b, hq, tp // bq, sp // bkv)
    g = hq // hkv
    return KernelContract(
        name=f"attn_flash[b{b} h{hq}/{hkv} t{t} s{s} d{d}]",
        route="attn_flash", domain="attention",
        grid=grid,
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        inputs=(
            BlockDecl("q", (1, 1, bq, d),
                      lambda bb, h, i, j: (bb, h, i, 0), (b, hq, tp, d),
                      itemsize),
            BlockDecl("k", (1, 1, bkv, d),
                      lambda bb, h, i, j: (bb, h // g, j, 0),
                      (b, hkv, sp, d), itemsize),
            BlockDecl("v", (1, 1, bkv, d),
                      lambda bb, h, i, j: (bb, h // g, j, 0),
                      (b, hkv, sp, d), itemsize),
        ),
        outputs=(BlockDecl("out", (1, 1, bq, d),
                           lambda bb, h, i, j: (bb, h, i, 0),
                           (b, hq, tp, d), itemsize),),
        scratch=(ScratchDecl("m", (bq, 128), 4),
                 ScratchDecl("l", (bq, 128), 4),
                 ScratchDecl("acc", (bq, d), 4)),
        acc_dims=(3,), guarded_init=True, guarded_store=True,
        vmem_budget=KERNEL_VMEM_BUDGET,
        extra_vmem_bytes=bq * bkv * 4,      # score tile (kernel body)
        admitted=flash_ok(t, s, d, itemsize),
        vmem_reject=not flash_ok(t, s, d, itemsize))


def _packed(hq: int, hkv: int, t: int, d: int, itemsize: int = 4
            ) -> KernelContract:
    bq, bkv = _heuristic_blocks(t, t, d, itemsize)
    bq = bkv = min(bq, bkv)
    tp = round_up(t, bq)
    grid = (hq, tp // bq, tp // bkv)
    g = hq // hkv
    return KernelContract(
        name=f"attn_packed_flash[h{hq}/{hkv} t{t} d{d}]",
        route="attn_packed_flash", domain="attention",
        grid=grid,
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        inputs=(
            BlockDecl("q", (1, bq, d), lambda h, i, j: (h, i, 0),
                      (hq, tp, d), itemsize),
            BlockDecl("k", (1, bkv, d), lambda h, i, j: (h // g, j, 0),
                      (hkv, tp, d), itemsize),
            BlockDecl("v", (1, bkv, d), lambda h, i, j: (h // g, j, 0),
                      (hkv, tp, d), itemsize),
            BlockDecl("seg_q", (1, bq), lambda h, i, j: (0, i), (1, tp), 4),
            BlockDecl("seg_k", (1, bkv), lambda h, i, j: (0, j), (1, tp), 4),
        ),
        outputs=(BlockDecl("out", (1, bq, d), lambda h, i, j: (h, i, 0),
                           (hq, tp, d), itemsize),),
        scratch=(ScratchDecl("m", (bq, 128), 4),
                 ScratchDecl("l", (bq, 128), 4),
                 ScratchDecl("acc", (bq, d), 4)),
        acc_dims=(2,), guarded_init=True, guarded_store=True,
        vmem_budget=KERNEL_VMEM_BUDGET,
        extra_vmem_bytes=bq * bkv * 4,
        admitted=flash_ok(t, t, d, itemsize),
        vmem_reject=not flash_ok(t, t, d, itemsize))


def _paged(b: int, hkv: int, g: int, d: int, page: int, n_log: int,
           n_layers: int = 2, itemsize: int = 4) -> KernelContract:
    gp = round_up(g, SUBLANE)
    n_phys = b * n_log                      # identity table's pool size
    tab = (np.arange(b, dtype=np.int32)[:, None] * n_log
           + np.arange(n_log, dtype=np.int32)[None, :])
    layer = n_layers - 1                    # the stack's far edge

    def kv_map(bb, j):
        return (layer, int(tab[bb, j]), 0, 0, 0)

    ok = (paged_decode_ok(page, hkv, d, itemsize)
          and skinny_ok(g, d, itemsize))
    return KernelContract(
        name=f"attn_decode[b{b} h{hkv} g{g} d{d} p{page}x{n_log} "
             f"l{n_layers}]",
        route="attn_decode_flash", domain="attn_decode",
        grid=(b, n_log),
        dimension_semantics=("parallel", "arbitrary"),
        inputs=(
            BlockDecl("q", (1, hkv, gp, d), lambda bb, j: (bb, 0, 0, 0),
                      (b, hkv, gp, d), itemsize),
            BlockDecl("k_pages", (1, 1, page, hkv, d), kv_map,
                      (n_layers, n_phys, page, hkv, d), itemsize),
            BlockDecl("v_pages", (1, 1, page, hkv, d), kv_map,
                      (n_layers, n_phys, page, hkv, d), itemsize),
        ),
        outputs=(BlockDecl("out", (1, hkv, gp, d),
                           lambda bb, j: (bb, 0, 0, 0),
                           (b, hkv, gp, d), itemsize),),
        scratch=(ScratchDecl("m", (hkv, gp, 128), 4),
                 ScratchDecl("l", (hkv, gp, 128), 4),
                 ScratchDecl("acc", (hkv, gp, d), 4)),
        acc_dims=(1,), guarded_init=True, guarded_store=True,
        vmem_budget=KERNEL_VMEM_BUDGET,
        extra_vmem_bytes=gp * page * 4,     # one head's score tile
        admitted=ok, vmem_reject=not ok,
        notes="KV index maps close over an identity block table and a "
              "layer index (scalar-prefetch indirection)")


def contracts() -> List[KernelContract]:
    return [
        _flash(2, 4, 2, 256, 256, 64),                 # GQA prefill
        _flash(1, 8, 8, 2048, 2048, 128),              # long MHA prefill
        _flash(2, 4, 2, 256, 256, 1 << 17),            # rejected: huge D
        _packed(4, 2, 1024, 64),                       # cu_seqlens batch
        _paged(2, 2, 4, 64, 64, 8),                    # paged decode
        _paged(2, 2, 4, 128, 1 << 15, 2),              # rejected: huge page
    ]
