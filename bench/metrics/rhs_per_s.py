"""Right-hand sides solved to their tolerance per second, credited by
iteration: the column-iterations that the window's chunks gave to live
requests, over the window's seconds times the mean iterations that this
run's converged requests took (both counted by the harness in whole
service chunks)."""


def read(run):
    iters = run.converged_iters()
    w = run.window
    if not iters or not w.seconds:
        return None
    mean_iters = sum(iters) / len(iters)
    return w.live_slot_chunks * run.chunk_iters / (w.seconds * mean_iters)
