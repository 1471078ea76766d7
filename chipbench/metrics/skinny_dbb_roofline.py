"""skinny_dbb_roofline (kernels, ``kernels/skinny``): the DBB GEMMs of at
most 32 rows, the decode projections that take the skinny kernel, as a
share of the v5e roofline.

Each op is a DBB GEMM custom call recognized by its operands (activation
[M, K], value plane [K nnz / block, N], mask plane [K / block, N]). Its
least time is the larger of its non-zero multiply-adds over the bf16 peak
and its packed weight plus activation bytes over HBM bandwidth; the share
is the sum of least times over the sum of the ops' device durations."""
from chipbench import rooflines


def read(run):
    return rooflines.gemm_share(
        run, rooflines.dbb_gemms(run, lambda m: m <= rooflines.SKINNY_M_MAX),
        "skinny_dbb_roofline")
