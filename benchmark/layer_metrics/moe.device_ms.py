"""Layer: graph and kernels. Device time a step under the scope ``moe``
(`mxnet_tpu/ops/moe_ops.py`): the router, the sort of the assignments, the
gather of their rows, the held experts' grouped products and the weighted
sum, forward, recomputed and backward, all layers; the grouped products'
kernels by their instruction's name (`grouped.py`)."""
from benchmark import grouped


def read(run):
    return grouped.device_ms_per_step(run, "moe")
