"""The three flash-attention kernels (`ops/attention.py`) against the larger
of operations over peak and bytes over bandwidth, both from shapes; every
call in the trace counts, the recomputed forward too."""
from benchmark.readers import roofline_share


def read(run):
    return roofline_share(
        run, ["_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel"])
