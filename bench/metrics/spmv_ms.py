"""Milliseconds of one SpMV of the registered operator at the traffic's
batch width: the device's busy time in the profiler's trace of repeated
calls after the window, over the number of calls."""


def read(run):
    seconds = run.spmv_seconds()
    return None if seconds is None else 1e3 * seconds
