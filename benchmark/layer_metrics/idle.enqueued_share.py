"""Layer: the device. Idle time of the traced stretch, as a share of the
stretch, after the next step program's enqueue instant (the end of its
step's `dispatch` phase) and before it starts on the device: the chip waits
for an operand, in `fit` the staged batch in flight. Reads the device
trace's gaps AND the program's step records (`stepprof.timeline()`), joined
on the trace's `profile_start_time` (`benchmark/timeline.py`); the six
`idle.*` shares sum to `device.idle_share`."""
from benchmark import timeline


def read(run):
    return timeline.read(run, "idle.enqueued_share")
