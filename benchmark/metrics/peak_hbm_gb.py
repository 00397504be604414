"""Peak bytes in use on the fullest device after the window, in GB."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
