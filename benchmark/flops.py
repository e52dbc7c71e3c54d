"""Operations and least bytes of a configuration's layers, from their
SHAPES (`reference.trace` over the plain layer list), never from XLA's
`cost_analysis`. 2 operations to a multiply-add.

Training runs three products to a convolution or dense layer: forward, the
gradient to its input and the gradient to its weight. The first layer's
input is the image, which needs no gradient, so that product is not counted:
recomputed or needless work is never credited."""
import numpy as np

from benchmark import reference


def matmul_layers(layers, shape, layout):
    """One dict to a convolution or dense layer, in execution order: name,
    op, macs (forward multiply-adds of the whole batch), and the elements of
    its input, weight and output."""
    out = []
    for layer, in_shape, out_shape, params in reference.trace(
            layers, shape, layout)[0]:
        if layer["op"] not in ("conv", "dense"):
            continue
        w = params[layer["name"] + "_weight"]
        # every output element takes one multiply-add per weight element
        # that feeds it: prod(w) / out_channels
        macs = int(np.prod(out_shape)) * int(np.prod(w[1:]))
        out.append({"name": layer["name"], "op": layer["op"], "macs": macs,
                    "in": int(np.prod(in_shape)), "w": int(np.prod(w)),
                    "out": int(np.prod(out_shape))})
    return out


def forward_macs_per_sample(layers, shape, layout):
    return sum(m["macs"] for m in matmul_layers(layers, shape, layout)) \
        / shape[0]


def train_flops(mats, op=None):
    """FLOPs of one training step over ``mats`` (`matmul_layers`), of the
    layers of kind ``op`` or of all. ``mats[0]`` is the network's first
    layer whatever ``op`` selects."""
    total = 0
    for i, m in enumerate(mats):
        if op is None or m["op"] == op:
            total += 2 * m["macs"] * (2 if i == 0 else 3)
    return total
