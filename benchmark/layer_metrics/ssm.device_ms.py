"""Layer: graph and kernels. Device time a step under the scope
``mamba2_ssd`` (`mxnet_tpu/ops/ssm_ops.py`): the chunked state-space scans
of all Mamba-2 layers, forward, recomputed and backward."""
from benchmark import scopes


def read(run):
    return scopes.device_ms_per_step(run, "mamba2_ssd")
