"""The device's ``peak_bytes_in_use`` after the window, in GB (1e9)."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
