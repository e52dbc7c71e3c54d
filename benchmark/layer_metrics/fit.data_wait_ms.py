"""Layer: the `Module.fit` loop. Median over the window's steps of the time
a batch spent in the `data_wait` phase (`_fit_loop` around the iterator's
`next` and `prepare`). The program's own step records,
`stepprof.timeline()`."""
from benchmark import timeline


def read(run):
    return timeline.read(run, "fit.data_wait_ms")
