"""ResNet v1 with bottleneck blocks, as He et al. (arXiv:1512.03385, Table 1)
describe it and as the Gluon model zoo lays it out: the stride of a stage's
first block sits on its first 1x1 convolution, the 1x1 convolutions carry a
bias and the 3x3 and the projection shortcut do not. Names follow the zoo's
counters (one convolution and one BatchNorm counter to a stage), because the
benchmark hands the same seeded weights to both sides by name."""
from benchmark.reference import (GAP, RELU, bn, conv, dense, maxpool,
                                 residual)


def layers(cfg):
    """``cfg``: prefix, stage_blocks, stage_channels (stem first), classes."""
    pre = cfg["prefix"]
    channels = cfg["stage_channels"]
    net = [conv(pre + "conv0", channels[0], 7, 2, 3, bias=False),
           bn(pre + "batchnorm0"), RELU, maxpool(3, 2, 1)]
    for stage, blocks in enumerate(cfg["stage_blocks"], 1):
        out, n = channels[stage], 0
        sp = "%sstage%d_" % (pre, stage)

        def unit(kernel, width, stride=1, pad=0, bias=True):
            nonlocal n
            pair = [conv("%sconv%d" % (sp, n), width, kernel, stride, pad,
                         bias), bn("%sbatchnorm%d" % (sp, n))]
            n += 1
            return pair

        for block in range(blocks):
            stride = 2 if block == 0 and stage > 1 else 1
            body = (unit(1, out // 4, stride) + [RELU]
                    + unit(3, out // 4, 1, 1, bias=False) + [RELU]
                    + unit(1, out))
            project = block == 0 and out != channels[stage - 1]
            shortcut = unit(1, out, stride, bias=False) if project else []
            net.append(residual(body, shortcut))
    return net + [GAP, dense(pre + "dense0", cfg["classes"])]
