"""Peak device memory in use over the run, in GB (1e9 bytes), on the
fullest of the cell's chips, as the device's allocator reports it."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
