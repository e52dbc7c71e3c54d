"""Layer: graph and kernels. The convolutions' share of the chip's peak
while they run: their FLOPs a step from the layers' SHAPES (`flops.py`,
forward and both gradients, the first layer's gradient to the image left
out), over the peak of the configuration's type times the chips, over
`conv.device_ms`. The fully connected layer's FLOPs are in `step.mfu` and
not here."""
from benchmark import flops, layer_index


def read(run):
    took = layer_index.device_ms(run, "convolution")
    work = run.result["work"]
    if took is None or run.peaks is None:
        return None
    mats = flops.matmul_layers(work["layers"], work["shape"], work["layout"])
    peak = run.peaks["flops_per_s"][work["dtype"]] * run.cell["chips"]
    return flops.train_flops(mats, op="conv") / peak / (took / 1e3) * 100.0
