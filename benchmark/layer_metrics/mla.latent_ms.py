"""Layer: graph and kernels. Device time a step under the scope
``mla_latent`` (`mxnet_tpu/models/moonlight.py`): latent attention's
down-projection to the latent and the shared rotary key, the latent's
norm, its up-projection to the heads' keys and values, both rotary terms
and the assembly of the 192-wide keys and queries, forward, recomputed and
backward, all layers. None on a program without the scope."""
from benchmark import scopes


def read(run):
    return scopes.device_ms_per_step(run, "mla_latent")
