"""Layer: graph and kernels. Device time a step of the step program's op
events attributed to ``pooling`` (`benchmark/layer_index.py`): the
windowed reductions and, for max pooling, autodiff's
``select_and_scatter``, where no convolution or BatchNorm is fused with
them. Read through the program's op index (`mxnet_tpu.shardprof.op_index`)."""
from benchmark import layer_index


def read(run):
    return layer_index.device_ms(run, "pooling")
