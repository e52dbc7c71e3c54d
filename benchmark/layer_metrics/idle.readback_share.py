"""Layer: the device. Idle time of the traced stretch, as a share of the
stretch, while the host was in `device_compute via=update_metric` with the
device already done: the outputs' copy to the host and the metric's
arithmetic. Reads the device trace's gaps AND the program's step records
(`stepprof.timeline()`), joined on the trace's `profile_start_time`
(`benchmark/timeline.py`); the six `idle.*` shares sum to
`device.idle_share`."""
from benchmark import timeline


def read(run):
    return timeline.read(run, "idle.readback_share")
