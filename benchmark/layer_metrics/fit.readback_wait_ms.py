"""Layer: the `Module.fit` loop. Median over the window's steps of the time
a batch spent in `device_compute via=update_metric` (`_fit_loop` around the
metric's update): a HOST WAIT for whatever of the step is still in flight,
not device time (that is `step.device_ms`). The program's own step records,
`stepprof.timeline()`."""
from benchmark import timeline


def read(run):
    return timeline.read(run, "fit.readback_wait_ms")
