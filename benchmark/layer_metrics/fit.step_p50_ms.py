"""Layer: the `Module.fit` loop. Median, over the window's dispatches, of
the interval between the last callback of one dispatch and of the next,
divided by the batches in a dispatch. The benchmark's own callback, host
clock."""
import numpy as np


def read(run):
    k = run.result["batches_per_dispatch"]
    ends = run.result["callback_times"][k - 1::k]
    if len(ends) < 2:
        return None
    return float(np.median(np.diff(ends))) / k * 1e3
