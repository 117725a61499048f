"""Device milliseconds of one iteration inside the service's chunk
program: the self time of every operation under the ``ghost.chunk``
scope, over the block iterations, in one chunk traced after the
window."""
import scoped


def read(run):
    return scoped.chunk_ms(run)
