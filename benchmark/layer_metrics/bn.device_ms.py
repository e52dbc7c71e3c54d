"""Layer: graph and kernels. Device time a step of the step program's op
events attributed to ``batch_norm`` and not to a convolution
(`benchmark/layer_index.py`): the statistics, the scale and shift, and the
custom VJP's backward sums and input gradient, where XLA did not fuse them
into a convolution. Read through the program's op index
(`mxnet_tpu.shardprof.op_index`)."""
from benchmark import layer_index


def read(run):
    return layer_index.device_ms(run, "batch_norm")
