"""Layer: the step recipe. Device time of the step program (the mix names
it, ``step_program``) on the trace's ``XLA Modules`` line, per batch."""
from benchmark import reduce


def read(run):
    if run.trace_data is None:
        return None
    seconds, runs = reduce.module_time(run.trace_data,
                                       run.traffic["step_program"])
    if not runs:
        return None
    return seconds / (runs * run.result["batches_per_dispatch"]) * 1e3
