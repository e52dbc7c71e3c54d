"""Layer: graph and kernels. Device time a step under the scope
``moe_dispatch`` (`mxnet_tpu/ops/moe_ops.py`): how the expert layer's rows
move, the sort of the assignments, the gather into the buffer of
assignments and the weighted gather back out, with both gathers' backward,
forward, recomputed and backward, all layers. The grouped products are not
in it. None on a program without the scope."""
from benchmark import scopes


def read(run):
    return scopes.device_ms_per_step(run, "moe_dispatch")
