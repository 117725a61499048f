"""Share of the traced window in which no operation ran on the device
(averaged over the cell's chips): in a traced run the profiler covers
the whole window, its refills included."""


def read(run):
    t = run.trace
    if t is None or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
