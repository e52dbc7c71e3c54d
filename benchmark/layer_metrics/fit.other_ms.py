"""Layer: the `Module.fit` loop. Median over the window's steps of the
step's wall less its `h2d`, `data_wait`, `dispatch` and read-back wait, a
batch: the loop's own Python, the throughput counters and the batch-end
callbacks. With those four it tiles the step. The program's own step
records, `stepprof.timeline()`."""
from benchmark import timeline


def read(run):
    return timeline.read(run, "fit.other_ms")
