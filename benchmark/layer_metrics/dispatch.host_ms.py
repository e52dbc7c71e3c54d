"""Layer: dispatch (`CompiledProgram`). Median over the window's steps of
the time a batch spent in the `dispatch` phase of `Module._step` /
`_step_scan`: the gather of the arguments, `FusedApplier.prepare` and the
compiled call until it returns. The program's own step records,
`stepprof.timeline()`."""
from benchmark import timeline


def read(run):
    return timeline.read(run, "dispatch.host_ms")
