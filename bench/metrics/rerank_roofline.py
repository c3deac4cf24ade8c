"""rerank_roofline (kernels): the re-rank work at the roofline over support_sqdist (the _sqdist_kernel call) device time."""
from bench.layers import kernel_roofline


def read(run):
    return kernel_roofline(run, "support_sqdist", "rerank")
