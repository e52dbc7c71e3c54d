"""Layer: graph and kernels. Device time a step under the scope
``attention`` (the model's attention mixer: q, k, v and o products and the
`flash_attention` kernel with its blocked backward), forward, recomputed
and backward."""
from benchmark import scopes


def read(run):
    return scopes.device_ms_per_step(run, "attention")
