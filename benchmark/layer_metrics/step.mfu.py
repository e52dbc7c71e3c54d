"""Layer: the step recipe. The whole step's share of the chip's peak:
FLOPs of forward and backward from the layers' SHAPES (`flops.py`), times
the rows finished per second over the traced stretch, over chips times the
peak of the configuration's type."""
from benchmark import flops


def read(run):
    trace, work = run.trace_data, run.result["work"]
    if trace is None or run.peaks is None:
        return None
    mats = flops.matmul_layers(work["layers"], work["shape"], work["layout"])
    per_step = flops.train_flops(mats)
    peak = run.peaks["flops_per_s"][work["dtype"]] * run.cell["chips"]
    steps = trace["dispatches"] * run.result["batches_per_dispatch"]
    return per_step * steps / trace["window_s"] / peak * 100.0
