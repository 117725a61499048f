"""7-point 3D Laplacian: 6 on the diagonal, -1 to each face neighbour
(hypre ``src/test/ij.c -laplacian``, one rank's ``nx**3`` subdomain)."""
from stencil import faces, sine_rhs, stencil3d


def generate(nx: int):
    return stencil3d(nx, faces(), 6.0, -1.0)


def rhs(rng, nx: int):
    return sine_rhs(rng, nx)
