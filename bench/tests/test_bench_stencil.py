"""The benchmark's matrix generators against dense stencils."""
import itertools

import numpy as np
import pytest

from benchtiny import harness


def dense_stencil(nx, diag, off, reach):
    """The stencil matrix built point by point from its definition."""
    n = nx ** 3
    A = np.zeros((n, n))
    for z, y, x in itertools.product(range(nx), repeat=3):
        i = z * nx * nx + y * nx + x
        for dz, dy, dx in itertools.product((-1, 0, 1), repeat=3):
            if sum(map(abs, (dz, dy, dx))) > reach:
                continue
            zz, yy, xx = z + dz, y + dy, x + dx
            if 0 <= zz < nx and 0 <= yy < nx and 0 <= xx < nx:
                j = zz * nx * nx + yy * nx + xx
                A[i, j] = diag if i == j else off
    return A


def densify(rows, cols, vals, n):
    A = np.zeros((n, n))
    np.add.at(A, (rows, cols), vals)
    return A


@pytest.mark.parametrize("name,diag,reach,nnz", [
    ("hpcg27", 26.0, 3, (3 * 4 - 2) ** 3),
    ("laplace7", 6.0, 1, 7 * 64 - 6 * 16)])
def test_generator_matches_the_dense_stencil(name, diag, reach, nnz):
    rows, cols, vals, n = harness.load_module("generators", name).generate(4)
    assert n == 64 and len(vals) == nnz
    A = densify(rows, cols, vals, n)
    np.testing.assert_array_equal(A, dense_stencil(4, diag, -1.0, reach))
    np.testing.assert_array_equal(A, A.T)
    assert (np.diag(A) == diag).all()


def test_entries_come_sorted_by_row_then_column():
    rows, cols, _, n = harness.load_module("generators", "hpcg27").generate(5)
    key = rows * n + cols
    assert (np.diff(key) > 0).all()


@pytest.mark.parametrize("name,nx,n,nnz", [
    ("hpcg27", 104, 1_124_864, 29_791_000),
    ("laplace7", 128, 2_097_152, 14_581_760)])
def test_full_sizes_follow_from_the_grid(name, nx, n, nnz):
    # nonzeros of a reach-r stencil: per axis (3 nx - 2) for the cube,
    # 7 n - 6 nx^2 for the faces
    if name == "hpcg27":
        assert (3 * nx - 2) ** 3 == nnz
    else:
        assert 7 * nx ** 3 - 6 * nx ** 2 == nnz
    assert nx ** 3 == n


@pytest.mark.parametrize("name", ["hpcg27", "laplace7"])
def test_the_sine_transform_diagonalises_the_stencil(name):
    from scipy.fft import idstn

    nx = 5
    gen = harness.load_module("generators", name)
    A = densify(*gen.generate(nx))
    S = idstn(np.eye(nx ** 3).reshape(-1, nx, nx, nx), type=1, norm="ortho",
              axes=(1, 2, 3)).reshape(nx ** 3, -1).T
    np.testing.assert_allclose(S.T @ S, np.eye(nx ** 3), atol=1e-12)
    D = S.T @ A @ S
    np.testing.assert_allclose(D, np.diag(np.diag(D)), atol=1e-10)


@pytest.mark.parametrize("name", ["hpcg27", "laplace7"])
def test_every_draw_has_unit_weight_on_every_eigenvector(name):
    from scipy.fft import dstn

    nx = 6
    gen = harness.load_module("generators", name)
    draws = [gen.rhs(np.random.default_rng([2 ** 33 + 1, i]), nx)
             for i in range(3)]
    for b in draws:
        assert b.dtype == np.float32 and b.shape == (nx ** 3,)
        coef = dstn(b.astype(np.float64).reshape(nx, nx, nx), type=1,
                    norm="ortho")
        np.testing.assert_allclose(np.abs(coef), 1.0, atol=1e-5)
    assert not np.array_equal(draws[0], draws[1])


def test_every_draw_asks_the_same_conjugate_gradient_iterations():
    # plain CG in float64 on the 7-point stencil: the residual history
    # depends only on the squared eigen-coefficients of b
    nx = 8
    gen = harness.load_module("generators", "laplace7")
    A = densify(*gen.generate(nx))

    def history(b):
        x, r = np.zeros_like(b), b.copy()
        p, rr, out = r.copy(), r @ r, []
        for _ in range(30):
            Ap = A @ p
            a = rr / (p @ Ap)
            x, r = x + a * p, r - a * Ap
            rr, rr_old = r @ r, rr
            out.append(np.sqrt(rr / (b @ b)))
            p = r + rr / rr_old * p
        return np.array(out)

    hs = [history(gen.rhs(np.random.default_rng([7, i]), nx)
                  .astype(np.float64)) for i in range(3)]
    for h in hs[1:]:
        np.testing.assert_allclose(h, hs[0], rtol=1e-6)
    normal = np.random.default_rng(7).standard_normal(nx ** 3)
    assert not np.allclose(history(normal), hs[0], rtol=1e-2)
