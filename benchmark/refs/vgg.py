"""VGG without BatchNorm, as Simonyan and Zisserman (arXiv:1409.1556, Table 1)
describe it: stacks of 3x3 convolutions with padding 1, each under a ReLU, a
2x2 max-pool after each stack, then two hidden dense layers of ``hidden``
units with dropout and the classifier. Names follow the Gluon model zoo's
counters, because the benchmark hands the same seeded weights to both sides
by name."""
from benchmark.reference import (FLATTEN, RELU, conv, dense, dropout,
                                 maxpool)


def layers(cfg):
    """``cfg``: prefix, stack_convs, stack_channels, hidden, dropout,
    classes."""
    pre = cfg["prefix"]
    net, n = [], 0
    for convs, width in zip(cfg["stack_convs"], cfg["stack_channels"]):
        for _ in range(convs):
            net += [conv("%sconv%d" % (pre, n), width, 3, 1, 1), RELU]
            n += 1
        net.append(maxpool(2, 2))
    net.append(FLATTEN)
    for i in range(2):
        net += [dense("%sdense%d" % (pre, i), cfg["hidden"]), RELU,
                dropout(cfg["dropout"])]
    return net + [dense(pre + "dense2", cfg["classes"])]
