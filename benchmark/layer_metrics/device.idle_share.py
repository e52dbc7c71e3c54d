"""Layer: the device. 1 - the union of the device's op intervals over the
traced stretch, mean of the chips used."""


def read(run):
    trace = run.trace_data
    if trace is None:
        return None
    return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0
