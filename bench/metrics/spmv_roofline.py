"""The SpMV's share of its roofline: its compulsory bytes and operations
(true nonzeros, one read of X and one write of Y at the batch width) at
the chip's peaks, over the device time ``spmv_ms`` measures."""
import work


def read(run):
    seconds = run.spmv_seconds()
    if run.peaks is None or seconds is None:
        return None
    st = run.cell.config["storage"]
    size = 2 if st["dtype"] == "bfloat16" else 4
    w = work.spmv(run.nnz(), run.prepared.coo.n, run.width,
                  val_bytes=size, vec_bytes=size)
    return work.roofline_pct(w, seconds, run.peaks)
