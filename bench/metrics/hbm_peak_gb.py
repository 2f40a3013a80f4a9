"""Peak bytes in use on the fullest chip after the window (GB)."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
