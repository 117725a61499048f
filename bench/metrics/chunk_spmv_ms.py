"""Device milliseconds of the SpMV in one iteration inside the service's
chunk program: the self time of the operations whose innermost scope is
``ghost.spmv`` (gather, row sums, alpha, beta, gamma; its fused dots are
``ghost.dots``), under ``ghost.chunk``, over the block iterations, in
one chunk traced after the window."""
import scoped


def read(run):
    return scoped.chunk_ms(run, "ghost.spmv")
