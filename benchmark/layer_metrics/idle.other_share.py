"""Layer: the device. Idle time of the traced stretch, as a share of the
stretch, while the host was in none of the named phases: the loop's own
Python, the counters, the callbacks, the seam between two steps. Reads the
device trace's gaps AND the program's step records (`stepprof.timeline()`),
joined on the trace's `profile_start_time` (`benchmark/timeline.py`); the
six `idle.*` shares sum to `device.idle_share`."""
from benchmark import timeline


def read(run):
    return timeline.read(run, "idle.other_share")
