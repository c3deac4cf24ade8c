"""aggregate_roofline (kernels): the aggregate work at the roofline over golden_support_aggregate (the _sagg_kernel call) device time."""
from bench.layers import kernel_roofline


def read(run):
    return kernel_roofline(run, "golden_support_aggregate", "aggregate")
