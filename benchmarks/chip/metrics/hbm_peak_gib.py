"""Peak device memory in use over the run (``peak_bytes_in_use``)."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 2**30
