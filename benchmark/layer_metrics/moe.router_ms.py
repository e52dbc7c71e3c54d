"""Layer: graph and kernels. Device time a step under the scope
``moe_router`` (`mxnet_tpu/ops/moe_ops.py`): the sigmoid router's scores,
the biased top-k selection, the loads and the selection bias's move, the
weights and the balance loss's gradient attached to the router's logits,
forward, recomputed and backward, all expert layers. None on a program
without the scope (the softmax router has none)."""
from benchmark import scopes


def read(run):
    return scopes.device_ms_per_step(run, "moe_router")
