"""Layer: the `Module.fit` loop. Median over the window's steps of the time
a batch spent in the `h2d` phase (`Module._load_batch`, or `stack_batches`
in the scan path; the phase's `bytes` say how much it staged), on
`perf_counter`. The program's own step records, `stepprof.timeline()`."""
from benchmark import timeline


def read(run):
    return timeline.read(run, "fit.stage_ms")
