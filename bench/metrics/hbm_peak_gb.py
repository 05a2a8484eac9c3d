"""Device memory: ``peak_bytes_in_use`` after the window, in GB (1e9 B)."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
