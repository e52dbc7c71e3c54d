"""Layer: graph and kernels. Share of the step program's op time (the sum
of its op events' durations) that no layer of `benchmark/layer_index.py`
claims: residual adds and activations left alone, the fully connected
layer, the loss head, the optimizer, copies. With `conv.device_ms`,
`bn.device_ms` and `pool.device_ms` it tiles the op time a step."""
from benchmark import layer_index


def read(run):
    got = layer_index.split(run)
    if got is None:
        return None
    total = sum(got.values())
    return got[None] / total * 100.0 if total else None
