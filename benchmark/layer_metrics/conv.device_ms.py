"""Layer: graph and kernels. Device time a step of the step program's op
events attributed to ``convolution`` (`benchmark/layer_index.py`): the
forward, the gradient to the input and the gradient to the weight of every
convolution, with whatever XLA fused into them (a BatchNorm's statistics in
the epilogue, say). Read through the program's op index
(`mxnet_tpu.shardprof.op_index`)."""
from benchmark import layer_index


def read(run):
    return layer_index.device_ms(run, "convolution")
