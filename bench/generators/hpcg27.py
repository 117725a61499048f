"""The HPCG matrix: a 27-point stencil with 26 on the diagonal and -1 to
each of the up to 26 neighbours (HPCG 3.1 ``GenerateProblem_ref.cpp``;
the default local grid of ``hpcg.dat`` is 104**3)."""
from stencil import cube, sine_rhs, stencil3d


def generate(nx: int):
    return stencil3d(nx, cube(), 26.0, -1.0)


def rhs(rng, nx: int):
    return sine_rhs(rng, nx)
