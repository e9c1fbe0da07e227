from repro.kernels.spmv.kernel import spmv_blocked, spmv_gs_pass, spmv_gs_pass_multi
from repro.kernels.spmv.ops import PallasGraph, pagerank_pallas
from repro.kernels.spmv.ref import spmv_blocked_ref, spmv_gs_pass_ref, spmv_ref

__all__ = [
    "spmv_blocked",
    "spmv_gs_pass",
    "spmv_gs_pass_multi",
    "PallasGraph",
    "pagerank_pallas",
    "spmv_blocked_ref",
    "spmv_gs_pass_ref",
    "spmv_ref",
]
