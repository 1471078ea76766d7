"""dbb_packed_roofline (kernels, ``kernels/dbb_gemm``): the DBB GEMMs of
more than 32 rows, the prefill projections that take the M-tiled kernel,
as a share of the v5e roofline, counted as in ``skinny_dbb_roofline``."""
from chipbench import rooflines


def read(run):
    return rooflines.gemm_share(
        run, rooflines.dbb_gemms(run, lambda m: m > rooflines.SKINNY_M_MAX),
        "dbb_packed_roofline")
