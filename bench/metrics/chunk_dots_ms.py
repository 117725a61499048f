"""Device milliseconds of the solver's reductions in one iteration
inside the service's chunk program: the self time of the operations
whose innermost scope is ``ghost.dots`` (the SpMV's fused dots and the
residual norms), under ``ghost.chunk``, over the block iterations, in
one chunk traced after the window."""
import scoped


def read(run):
    return scoped.chunk_ms(run, "ghost.dots")
