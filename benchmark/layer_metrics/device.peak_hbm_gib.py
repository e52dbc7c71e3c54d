"""Layer: the device. `memory_stats()["peak_bytes_in_use"]` of the fullest
chip, read when the window has closed and before the reference runs."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 2**30
