"""Constant-coefficient 3D stencil matrices as host-side COO.

Rows are numbered ``i = z * nx**2 + y * nx + x`` on an ``nx**3`` grid
with Dirichlet boundaries: a stencil point that falls outside the grid
is dropped.  Entries come out sorted by row, then by column.
"""
from __future__ import annotations

import itertools

import numpy as np


def stencil3d(nx: int, offsets, diag: float, off: float):
    """COO ``(rows, cols, vals, n)`` of the stencil ``offsets`` (a list of
    ``(dz, dy, dx)``, the centre included) with ``diag`` at the centre
    and ``off`` at every other point."""
    nx = int(nx)
    n = nx ** 3
    offsets = sorted(offsets, key=lambda o: o[0] * nx * nx + o[1] * nx + o[2])
    i = np.arange(n, dtype=np.int64)
    z, y, x = i // (nx * nx), (i // nx) % nx, i % nx
    cols = np.empty((n, len(offsets)), np.int64)
    keep = np.empty((n, len(offsets)), bool)
    vals = np.empty(len(offsets), np.float64)
    for k, (dz, dy, dx) in enumerate(offsets):
        cols[:, k] = i + dz * nx * nx + dy * nx + dx
        keep[:, k] = ((0 <= z + dz) & (z + dz < nx) & (0 <= y + dy)
                      & (y + dy < nx) & (0 <= x + dx) & (x + dx < nx))
        vals[k] = diag if (dz, dy, dx) == (0, 0, 0) else off
    rows = np.broadcast_to(i[:, None], cols.shape)[keep]
    v = np.broadcast_to(vals[None, :], cols.shape)[keep]
    return rows, cols[keep], np.ascontiguousarray(v), n


def sine_rhs(rng, nx: int) -> np.ndarray:
    """A right-hand side of unit weight on every eigenvector, with signs
    from ``rng``: ``b = S s`` with ``S`` the orthonormal 3D sine
    transform (DST-I) and ``s`` a vector of random signs.

    ``S`` diagonalises every stencil of ``stencil3d`` that is symmetric
    in each axis (a sum of Kronecker products of ``I`` and
    ``tridiag(1, 0, 1)``), as both generators' are.  Conjugate gradients
    converge on ``b`` as the squared coefficients of ``b`` in the
    eigenbasis dictate, and those are all 1: every draw asks the same
    work, in exact arithmetic to the iteration, while the values differ.
    A standard normal ``b`` has the same weights on average."""
    from scipy.fft import idstn

    nx = int(nx)
    signs = rng.integers(0, 2, size=(nx, nx, nx), dtype=np.int8)
    s = signs.astype(np.float32) * 2 - 1
    return idstn(s, type=1, norm="ortho", workers=1).reshape(-1)


def faces():
    """The 7-point stencil: the centre and its six face neighbours."""
    return [o for o in itertools.product((-1, 0, 1), repeat=3)
            if sum(map(abs, o)) <= 1]


def cube():
    """The 27-point stencil: the centre and all 26 neighbours."""
    return list(itertools.product((-1, 0, 1), repeat=3))
