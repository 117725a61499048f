"""Share of the batch's column slots that held a live request, over the
window's chunks (service: retire and refill leave slots empty)."""


def read(run):
    w = run.window
    if not w.chunks:
        return None
    return 100.0 * w.live_slot_chunks / (run.width * w.chunks)
