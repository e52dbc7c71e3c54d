"""Layer: the step recipe. The whole step's share of the chip's peak for a
token model: operations of forward and backward from the configuration's
SHAPES (`flops_tokens.py`; recomputation is never credited), times the
steps finished per second over the traced stretch, over chips times the
peak of the configuration's operand type."""
from benchmark import flops_tokens


def read(run):
    trace, work = run.trace_data, run.result["work"]
    if trace is None or run.peaks is None or "config" not in work:
        return None
    cfg = work["config"]
    per_step = flops_tokens.train_flops(cfg, work["rows"], work["tokens"])
    peak = run.peaks["flops_per_s"][cfg["dtype"]] * run.cell["chips"]
    steps = trace["dispatches"] * run.result["batches_per_dispatch"]
    return per_step * steps / trace["window_s"] / peak * 100.0
