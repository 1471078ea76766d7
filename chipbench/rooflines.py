"""Shared by the DBB GEMM roofline readers (``metrics/*_roofline.py``)."""
from __future__ import annotations

from chipbench import shapes, work

SKINNY_M_MAX = 32      # rows up to which the program takes the skinny kernel


def dbb_gemms(run, keep) -> list:
    """(op, (M, K, N)) of the traced DBB GEMM custom calls whose M passes
    ``keep``."""
    b, z = run.cfg["dbb"]["block"], run.cfg["dbb"]["nnz"]
    out = []
    for o in run.reading.ops:
        mkn = shapes.dbb_gemm_mkn(o.name, b, z)
        if mkn is not None and keep(mkn[0]):
            out.append((o, mkn))
    return out


def gemm_share(run, found, label: str):
    """Sum of the ops' least times over the sum of their device times;
    None when there are no ops."""
    if not found:
        return None
    ops = sum(work.dbb_flops(run.cfg, *mkn) for _, mkn in found)
    nbytes = sum(work.dbb_gemm_bytes(run.cfg, *mkn) for _, mkn in found)
    seconds = sum(o.dur_ns for o, _ in found) / 1e9
    value, bound = work.roofline_share(ops, nbytes, seconds, run.peaks)
    print(f"{label}: {len(found)} ops, {seconds!r} s on the device, "
          f"{ops!r} ops, {nbytes!r} bytes, {bound}-bound", flush=True)
    return value
