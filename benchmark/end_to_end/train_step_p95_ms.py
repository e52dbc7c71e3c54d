"""95th percentile over ALL intervals between consecutive
`batch_end_callback` calls of the window. Host clock."""
import numpy as np


def read(run):
    times = run.result["callback_times"]
    if len(times) < 3:
        return None
    return float(np.percentile(np.diff(times), 95)) * 1e3
