"""The softmax-cross-entropy pair (`ops/fused_ops.py`), bound by bytes."""
from benchmark.readers import roofline_share


def read(run):
    return roofline_share(run, ["_xent_fwd_kernel", "_xent_bwd_kernel"])
