"""Solver correctness: CG / pipelined CG / MinRes / Lanczos / KPM / ChebFD
on the paper's application matrices."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import from_coo
from repro.matrices import anderson3d, laplace3d, matpde, spin_chain_xx
from repro.solvers import (cg, chebfd, kpm_dos_moments, lanczos_extrema,
                           make_operator, minres, pipelined_cg)
from repro.solvers.kpm import jackson_kernel
from repro.solvers.operator import MatrixFreeOperator


@pytest.fixture(scope="module")
def lap():
    r, c, v, n = laplace3d(7)
    A = from_coo(r, c, v, (n, n), C=16, sigma=32, w_align=4, dtype=np.float32)
    Ad = np.zeros((n, n), np.float32)
    Ad[r, c] += v.astype(np.float32)
    return A, Ad, n


class TestCG:
    def test_solves_block(self, lap, rng):
        A, Ad, n = lap
        op = make_operator(A)
        b = rng.standard_normal((n, 3)).astype(np.float32)
        res = cg(op, A.permute(b), tol=1e-7, maxiter=500)
        x = np.asarray(A.unpermute(res.x))
        assert bool(np.asarray(res.converged).all())
        assert np.abs(Ad @ x - b).max() < 1e-4

    def test_single_vector(self, lap, rng):
        A, Ad, n = lap
        op = make_operator(A)
        b = rng.standard_normal(n).astype(np.float32)
        res = cg(op, A.permute(b), tol=1e-7)
        assert np.abs(Ad @ np.asarray(A.unpermute(res.x)) - b).max() < 1e-4

    def test_pipelined_matches_cg(self, lap, rng):
        A, Ad, n = lap
        op = make_operator(A)
        b = rng.standard_normal((n, 2)).astype(np.float32)
        r1 = cg(op, A.permute(b), tol=1e-7, maxiter=400)
        r2 = pipelined_cg(op, A.permute(b), tol=1e-7, maxiter=400)
        x1 = np.asarray(A.unpermute(r1.x))
        x2 = np.asarray(A.unpermute(r2.x))
        np.testing.assert_allclose(x1, x2, atol=1e-3)

    def test_matrix_free(self, lap, rng):
        """Paper 5.1: custom SpMV function pointer (matrix-free hook)."""
        A, Ad, n = lap
        ghost_op = make_operator(A)
        op = MatrixFreeOperator(lambda x: ghost_op.mv(x), ghost_op.n,
                                np.float32)
        b = rng.standard_normal((n, 1)).astype(np.float32)
        res = cg(op, A.permute(b), tol=1e-6)
        assert bool(np.asarray(res.converged).all())


class TestMinres:
    def test_indefinite(self, lap, rng):
        A, Ad, n = lap
        # shift to make indefinite but safely nonsingular
        r, c = np.nonzero(Ad)
        v = Ad[r, c].astype(np.float64)
        shift = 2.7183           # irrational: far from lattice eigenvalues
        r2 = np.concatenate([r, np.arange(n)])
        c2 = np.concatenate([c, np.arange(n)])
        v2 = np.concatenate([v, -shift * np.ones(n)])
        As = from_coo(r2, c2, v2, (n, n), C=8, sigma=16, dtype=np.float32)
        op = make_operator(As)
        b = rng.standard_normal(n).astype(np.float32)
        res = minres(op, As.permute(b), tol=1e-7, maxiter=1500)
        x = np.asarray(As.unpermute(res.x))
        rel = np.abs((Ad - shift * np.eye(n)) @ x - b).max() / np.abs(b).max()
        assert rel < 1e-2, rel


class TestLanczos:
    def test_extrema_bracket_spectrum(self, lap):
        A, Ad, n = lap
        lo, hi = lanczos_extrema(make_operator(A), k=40)
        ev = np.linalg.eigvalsh(Ad.astype(np.float64))
        assert lo <= ev[0] + 1e-3
        assert hi >= ev[-1] - 1e-3


class TestKPM:
    def test_fused_equals_naive(self, lap):
        """The augmented-SpMV KPM (paper's 2.5x fusion showcase) must give
        identical moments to the unfused 3-kernel variant."""
        A, Ad, n = lap
        op = make_operator(A)
        lo, hi = lanczos_extrema(op, k=30)
        mf = kpm_dos_moments(op, 32, n_probes=2, spectrum=(lo, hi), fused=True)
        mn = kpm_dos_moments(op, 32, n_probes=2, spectrum=(lo, hi), fused=False)
        np.testing.assert_allclose(np.asarray(mf), np.asarray(mn),
                                   rtol=1e-3, atol=1e-5)

    def test_moments_match_exact_trace(self, lap):
        """mu_m ~ tr(T_m(As))/n: check against dense eigendecomposition.

        The operator acts on the SELL-padded space (nrows_pad), whose
        padding rows contribute exact zero eigenvalues — they must be in
        both the spectrum window (else Chebyshev diverges outside [-1,1])
        and the exact trace."""
        A, Ad, n = lap
        op = make_operator(A)
        ev = np.linalg.eigvalsh(Ad.astype(np.float64))
        ev_pad = np.concatenate([ev, np.zeros(A.nrows_pad - n)])
        lo, hi = min(ev[0], 0.0) - 0.1, ev[-1] + 0.1
        a, g = (hi - lo) / 2, (hi + lo) / 2
        evs = (ev_pad - g) / a
        M = 16
        mus = np.asarray(kpm_dos_moments(op, M, n_probes=24,
                                         spectrum=(lo, hi), seed=1))
        exact = np.array([np.mean(np.cos(m * np.arccos(np.clip(evs, -1, 1))))
                          for m in range(M)])
        # stochastic trace estimator: loose tolerance
        np.testing.assert_allclose(mus, exact, atol=0.3)

    def test_jackson_kernel_properties(self):
        g = jackson_kernel(64)
        assert abs(g[0] - 1.0) < 1e-12
        assert (np.diff(g) <= 1e-12).all()          # monotone decreasing
        assert g[-1] > 0


class TestChebFD:
    def test_interior_eigenvalues_anderson(self):
        """Chebyshev filter diagonalization on a disordered Hamiltonian
        (the ESSEX application domain)."""
        r, c, v, n = anderson3d(6, disorder=2.0, seed=3)
        A = from_coo(r, c, v, (n, n), C=16, sigma=32, dtype=np.float32)
        Ad = np.zeros((n, n)); Ad[r, c] += v
        ev = np.linalg.eigvalsh(Ad)
        op = make_operator(A)
        lo, hi = lanczos_extrema(op, k=40)
        target = (float(ev[0] - 0.1), float(ev[3] + 0.01))
        res = chebfd(op, target, block_size=6, degree=100, sweeps=6,
                     spectrum=(lo, hi))
        found = res.eigenvalues[res.residuals < 1e-2]
        assert len(found) >= 3
        for f in found[:3]:
            assert np.abs(ev - f).min() < 5e-3

    def test_pallas_tsm_path(self, lap):
        A, Ad, n = lap
        op = make_operator(A)
        ev = np.linalg.eigvalsh(Ad.astype(np.float64))
        lo_t, hi_t = float(ev[0] - 0.1), float(ev[3] + 0.02)
        # spectrum bound must include the SELL padding rows' exact zero
        # eigenvalues (Chebyshev diverges outside the scaled [-1, 1])
        res = chebfd(op, (lo_t, hi_t), block_size=6, degree=80, sweeps=5,
                     spectrum=(-0.2, float(ev[-1]) + 0.2),
                     use_pallas_tsm=True)
        # converged Ritz values inside the window (the SELL padding rows
        # contribute exact zero eigenvalues outside the target window)
        good = res.eigenvalues[(res.residuals < 5e-2)
                               & (res.eigenvalues > lo_t - 0.05)
                               & (res.eigenvalues < hi_t + 0.05)]
        assert len(good) >= 1
        for g in good:
            assert np.abs(ev - g).min() < 5e-2


class TestDtypeFidelity:
    """Internally generated start vectors follow op.dtype (f64 operators
    must not be silently downcast) and complex-Hermitian reorth uses the
    conjugate transpose."""

    def test_lanczos_f64(self):
        from repro.solvers import lanczos
        with jax.enable_x64(True):
            r, c, v, n = laplace3d(6)
            A = from_coo(r, c, v, (n, n), C=16, sigma=32, dtype=np.float64)
            op = make_operator(A)
            assert op.dtype == np.float64
            res = lanczos(op, None, 30, reorth=True, keep_basis=True)
            assert res.alphas.dtype == np.float64
            assert res.V.dtype == np.float64
            lo, hi = lanczos_extrema(op, k=40)
            Ad = np.zeros((n, n)); Ad[r, c] += v
            ev = np.linalg.eigvalsh(Ad)
            assert lo <= ev[0] + 1e-8 and hi >= ev[-1] - 1e-8

    def test_chebfd_f64(self):
        with jax.enable_x64(True):
            r, c, v, n = laplace3d(5)
            A = from_coo(r, c, v, (n, n), C=8, sigma=16, dtype=np.float64)
            Ad = np.zeros((n, n)); Ad[r, c] += v
            ev = np.linalg.eigvalsh(Ad)
            op = make_operator(A)
            target = (float(ev[0] - 0.1), float(ev[2] + 0.01))
            res = chebfd(op, target, block_size=4, degree=80, sweeps=5,
                         spectrum=(min(ev[0], 0.0) - 0.2, ev[-1] + 0.2))
            assert res.eigenvectors.dtype == np.float64
            found = res.eigenvalues[res.residuals < 1e-2]
            assert len(found) >= 1
            for f in found[:2]:
                assert np.abs(ev - f).min() < 5e-3

    def test_cg_f64_tiny_floor(self, rng):
        with jax.enable_x64(True):
            r, c, v, n = laplace3d(5)
            A = from_coo(r, c, v, (n, n), C=8, sigma=16, dtype=np.float64)
            op = make_operator(A)
            b = A.permute(rng.standard_normal(n))
            res = cg(op, b, tol=1e-12, maxiter=500)
            assert res.x.dtype == np.float64
            assert bool(np.asarray(res.converged))
            # an f64 solve can genuinely reach below f32 resolution
            assert float(res.resnorm) < 1e-10 * np.linalg.norm(np.asarray(b))

    def test_lanczos_happy_breakdown(self, rng):
        """On A = I every start vector is an eigenvector: beta_1 = 0 and
        the recurrence used to keep iterating on the zero vector, padding
        garbage alphas that dragged a spurious 0 into the tridiagonal
        spectrum.  Now nvalid reports the usable prefix and the extrema
        bracket stays tight around 1."""
        from repro.solvers import lanczos
        n = 64
        op = MatrixFreeOperator(lambda x: x, n, np.float32)
        v0 = np.zeros(n, np.float32)
        v0[0] = 1.0                     # exact eigenvector: w = v - 1*v = 0
        res = lanczos(op, jnp.asarray(v0), 12, keep_basis=True)
        assert int(res.nvalid) == 1
        # frozen steps write nothing: zero padding past the valid prefix
        assert np.allclose(np.asarray(res.alphas[1:]), 0.0)
        assert np.allclose(np.asarray(res.betas), 0.0)
        assert np.allclose(np.asarray(res.V[:, 1:]), 0.0)
        np.testing.assert_allclose(float(res.alphas[0]), 1.0, rtol=1e-6)
        # extrema on a 1-d operator: the random start is +-1 exactly, so
        # the recurrence breaks down after one step; the padded zero
        # alphas used to drag a spurious 0 into the bracket (lo ~ -0.05)
        op1 = MatrixFreeOperator(lambda x: 2.0 * x, 1, np.float32)
        lo, hi = lanczos_extrema(op1, k=12)
        assert lo > 1.8 and hi < 2.2 and lo <= 2.0 <= hi

    def test_lanczos_no_breakdown_unchanged(self, rng):
        """The breakdown masks are inert on a healthy run: full nvalid
        and the same recurrence values as before the guard."""
        from repro.solvers import lanczos
        r, c, v, n = laplace3d(6)
        A = from_coo(r, c, v, (n, n), C=16, sigma=32, dtype=np.float32)
        op = make_operator(A)
        res = lanczos(op, None, 20, seed=3)
        assert int(res.nvalid) == 20
        assert np.all(np.asarray(res.betas) > 0)

    def test_lanczos_complex_hermitian_reorth(self, rng):
        """Regression: reorthogonalization must project with V^H, not V^T.

        On a complex Hermitian operator the V^T variant destroys the
        basis; with V^H the Ritz extrema match the dense spectrum."""
        import jax.numpy as jnp
        from repro.solvers import lanczos
        from repro.solvers.lanczos import tridiag_eigh

        n = 48
        H = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
        H = ((H + H.conj().T) / 2).astype(np.complex64)
        Hj = jnp.asarray(H)
        op = MatrixFreeOperator(lambda x: Hj @ x, n, np.complex64)
        res = lanczos(op, None, n, reorth=True, keep_basis=True, seed=2)
        # real tridiagonal coefficients, complex basis
        assert res.alphas.dtype == np.float32
        assert res.V.dtype == np.complex64
        # the reorthogonalized basis stays unitary to working precision
        G = np.asarray(res.V.conj().T @ res.V)
        np.testing.assert_allclose(G, np.eye(n), atol=5e-3)
        ev_dense = np.linalg.eigvalsh(H.astype(np.complex128))
        ev_lan, _ = tridiag_eigh(res.alphas, res.betas)
        np.testing.assert_allclose(ev_lan[0], ev_dense[0], atol=1e-2)
        np.testing.assert_allclose(ev_lan[-1], ev_dense[-1], atol=1e-2)


class TestQuantumMatrices:
    def test_spin_chain_indefinite_minres(self, rng):
        """'Completely indefinite, no mesh interpretation' matrices
        (paper 1.3) — XXZ chain."""
        r, c, v, n = spin_chain_xx(8)
        A = from_coo(r, c, v, (n, n), C=16, sigma=32, dtype=np.float32)
        Ad = np.zeros((n, n)); Ad[r, c] += v
        op = make_operator(A)
        b = rng.standard_normal(n).astype(np.float32)
        res = minres(op, A.permute(b), tol=1e-6, maxiter=2000)
        x = np.asarray(A.unpermute(res.x))
        rel = np.abs(Ad @ x - b).max() / np.abs(b).max()
        assert rel < 1e-2
