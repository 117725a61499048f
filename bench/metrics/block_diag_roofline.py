"""The block-diagonal preconditioner's share of its roofline: the
inverse blocks, one read of X and one write of Y at the batch width, at
the chip's peaks, over the device time of one application in the
profiler's trace of repeated calls after the window.  Read only where
the traffic asks for ``block_jacobi:<bs>``."""
import work


def read(run):
    spec = run.cell.traffic.get("precond") or ""
    kind, _, bs = spec.partition(":")
    if kind != "block_jacobi" or not bs or run.peaks is None:
        return None
    seconds = run.precond_seconds()
    if seconds is None:
        return None
    size = 2 if run.cell.config["storage"]["dtype"] == "bfloat16" else 4
    w = work.block_diag(run.prepared.coo.n, int(bs), run.width,
                        val_bytes=size, vec_bytes=size)
    return work.roofline_pct(w, seconds, run.peaks)
