"""Layer: the `Module.fit` loop. The proof that the program's step records
and the device trace share a clock (`benchmark/timeline.py`): executions of
the step program in the traced stretch that start before their own step's
`dispatch` phase or end after its last read-back wait, plus the difference
between the steps entered in the stretch and the trace's dispatches. Reads
the program's spans AND the device trace; must read 0."""
from benchmark import timeline


def read(run):
    return timeline.read(run, "timeline.unmatched_steps")
