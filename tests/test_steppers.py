"""Stepper-form solvers: chunked composition is bit-identical to the
monolithic entry points, states merge column-wise, and the matrix-free
operator's fused dots match the SELL-C-sigma path exactly."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import from_coo
from repro.core.spmv import SpmvOpts
from repro.matrices import laplace3d
from repro.solvers import (cg, cg_finalize, cg_init, cg_step, make_operator,
                           merge_columns, minres, minres_finalize,
                           minres_init, minres_step, pipelined_cg,
                           pipelined_cg_finalize, pipelined_cg_init,
                           pipelined_cg_step)
from repro.solvers.operator import MatrixFreeOperator


@pytest.fixture(scope="module")
def lap():
    r, c, v, n = laplace3d(7)
    A = from_coo(r, c, v, (n, n), C=16, sigma=32, w_align=4, dtype=np.float32)
    Ad = np.zeros((n, n), np.float32)
    Ad[r, c] += v.astype(np.float32)
    return A, Ad, n


def _compose(init, step, fin, op, b, tol, maxiter, k):
    state = init(op, b, tol=tol, maxiter=maxiter)
    for _ in range(maxiter // k + 1):
        state = step(op, state, k)
    return state


class TestChunkedEqualsMonolithic:
    """cg/pipelined_cg/minres are compositions of their steppers; chunked
    composition with any chunk size must reproduce them bit for bit —
    including chunk=1 (every boundary) and chunk>maxiter (one chunk)."""

    @pytest.mark.parametrize("k", [1, 7, 100, 400])
    def test_cg(self, lap, rng, k):
        A, Ad, n = lap
        op = make_operator(A)
        b = A.permute(rng.standard_normal((n, 3)).astype(np.float32))
        ref = cg(op, b, tol=1e-7, maxiter=200)
        st = _compose(cg_init, cg_step, cg_finalize, op, b, 1e-7, 200, k)
        res = cg_finalize(st)
        assert np.array_equal(np.asarray(ref.x), np.asarray(res.x))
        assert int(ref.iters) == int(res.iters)
        assert np.array_equal(np.asarray(ref.resnorm), np.asarray(res.resnorm))
        assert np.array_equal(np.asarray(ref.converged),
                              np.asarray(res.converged))

    @pytest.mark.parametrize("k", [3, 50])
    def test_pipelined_cg(self, lap, rng, k):
        A, Ad, n = lap
        op = make_operator(A)
        b = A.permute(rng.standard_normal((n, 2)).astype(np.float32))
        ref = pipelined_cg(op, b, tol=1e-6, maxiter=150)
        st = _compose(pipelined_cg_init, pipelined_cg_step,
                      pipelined_cg_finalize, op, b, 1e-6, 150, k)
        res = pipelined_cg_finalize(st)
        assert np.array_equal(np.asarray(ref.x), np.asarray(res.x))
        assert int(ref.iters) == int(res.iters)

    @pytest.mark.parametrize("k", [1, 5, 64, 500])
    def test_minres(self, lap, rng, k):
        A, Ad, n = lap
        op = make_operator(A)
        b = A.permute(rng.standard_normal((n, 2)).astype(np.float32))
        ref = minres(op, b, tol=1e-6, maxiter=300)
        st = _compose(minres_init, minres_step, minres_finalize,
                      op, b, 1e-6, 300, k)
        res = minres_finalize(st)
        assert np.array_equal(np.asarray(ref.x), np.asarray(res.x))
        assert int(ref.iters) == int(res.iters)
        assert np.array_equal(np.asarray(ref.resnorm), np.asarray(res.resnorm))

    @pytest.mark.parametrize("k", [1, 9, 300])
    def test_cg_complex64(self, rng, k):
        """complex64 solves go through the same steppers (conjugated
        norms engage only for complex dtypes); chunked composition stays
        bit-identical."""
        n = 48
        B = (rng.standard_normal((n, n))
             + 1j * rng.standard_normal((n, n)))
        H = (B @ B.conj().T + n * np.eye(n)).astype(np.complex64)
        r, c = np.nonzero(H)
        A = from_coo(r, c, H[r, c], (n, n), C=8, sigma=16,
                     dtype=np.complex64)
        op = make_operator(A)
        b = A.permute((rng.standard_normal((n, 2))
                       + 1j * rng.standard_normal((n, 2))
                       ).astype(np.complex64))
        ref = cg(op, b, tol=1e-6, maxiter=200)
        assert bool(np.all(np.asarray(ref.converged)))
        st = _compose(cg_init, cg_step, cg_finalize, op, b, 1e-6, 200, k)
        res = cg_finalize(st)
        assert np.array_equal(np.asarray(ref.x), np.asarray(res.x))
        assert int(ref.iters) == int(res.iters)
        # the solve is actually right (Hermitian PD, conjugated dots)
        x = np.asarray(A.unpermute(res.x))
        bb = np.asarray(A.unpermute(b))
        assert np.abs(H @ x - bb).max() / np.abs(bb).max() < 1e-3

    @pytest.mark.parametrize("k", [1, 11, 400])
    def test_minres_complex64(self, rng, k):
        n = 40
        B = (rng.standard_normal((n, n))
             + 1j * rng.standard_normal((n, n)))
        H = ((B + B.conj().T) / 2 + n * np.eye(n)).astype(np.complex64)
        r, c = np.nonzero(H)
        A = from_coo(r, c, H[r, c], (n, n), C=8, sigma=8,
                     dtype=np.complex64)
        op = make_operator(A)
        b = A.permute((rng.standard_normal(n)
                       + 1j * rng.standard_normal(n)).astype(np.complex64))
        ref = minres(op, b, tol=1e-5, maxiter=300)
        st = _compose(minres_init, minres_step, minres_finalize,
                      op, b, 1e-5, 300, k)
        res = minres_finalize(st)
        assert np.array_equal(np.asarray(ref.x), np.asarray(res.x[:, 0]))
        assert int(ref.iters) == int(res.iters)
        x = np.asarray(A.unpermute(res.x[:, 0]))
        bb = np.asarray(A.unpermute(b))
        assert np.abs(H @ x - bb).max() / np.abs(bb).max() < 1e-3

    def test_1d_entry_points_unchanged(self, lap, rng):
        A, Ad, n = lap
        op = make_operator(A)
        b = A.permute(rng.standard_normal(n).astype(np.float32))
        for solve in (cg, pipelined_cg, minres):
            res = solve(op, b, tol=1e-6, maxiter=300)
            assert res.x.ndim == 1 and res.resnorm.ndim == 0

    def test_step_early_exit_when_all_done(self, lap, rng):
        """Once every column converged, further chunks are no-ops (the
        iteration counter must not keep running)."""
        A, Ad, n = lap
        op = make_operator(A)
        b = A.permute(rng.standard_normal((n, 2)).astype(np.float32))
        st = cg_init(op, b, tol=1e-6, maxiter=500)
        st = cg_step(op, st, 500)
        it0 = int(st.it)
        st2 = cg_step(op, st, 50)
        assert int(st2.it) == it0
        assert np.array_equal(np.asarray(st.x), np.asarray(st2.x))


class TestPrecondNoneIsPR3Path:
    """Threading M through the steppers must not perturb the plain path:
    ``precond=None`` states keep the PR-3 layout and ``M=None`` solves
    are bit-identical to calls that never mention M."""

    # the PR-3 state layouts, pinned: adding/removing/reordering fields
    # changes the while_loop carry (and the service's merge semantics)
    CG_FIELDS = ("x", "r", "p", "rr", "tol2", "it", "maxiter", "done")
    PCG_FIELDS = ("x", "r", "w", "z", "s", "p", "gamma_prev", "alpha_prev",
                  "tol2", "fresh", "it", "maxiter", "done")
    MINRES_FIELDS = ("x", "v", "v_old", "w", "w_old", "beta", "eta", "c",
                     "c_old", "s", "s_old", "resn", "tolb", "it", "maxiter",
                     "done")

    def test_state_layouts_pinned(self):
        from repro.solvers import CGState, MinresState, PCGState
        assert CGState._fields == self.CG_FIELDS
        assert PCGState._fields == self.PCG_FIELDS
        assert MinresState._fields == self.MINRES_FIELDS

    def test_init_returns_plain_states(self, lap, rng):
        from repro.solvers import CGState, MinresState
        A, Ad, n = lap
        op = make_operator(A)
        b = A.permute(rng.standard_normal((n, 2)).astype(np.float32))
        assert type(cg_init(op, b)) is CGState
        assert type(cg_init(op, b, M=None)) is CGState
        assert type(minres_init(op, b)) is MinresState
        assert type(minres_init(op, b, M=None)) is MinresState

    def test_explicit_none_bit_identical(self, lap, rng):
        """cg/minres with M=None spelled out == the no-kwarg call, bit
        for bit (same states, same chunks, same cache entries)."""
        A, Ad, n = lap
        op = make_operator(A)
        b = A.permute(rng.standard_normal((n, 3)).astype(np.float32))
        r1 = cg(op, b, tol=1e-7, maxiter=200)
        r2 = cg(op, b, tol=1e-7, maxiter=200, M=None)
        assert np.array_equal(np.asarray(r1.x), np.asarray(r2.x))
        assert int(r1.iters) == int(r2.iters)
        m1 = minres(op, b, tol=1e-6, maxiter=200)
        m2 = minres(op, b, tol=1e-6, maxiter=200, M=None)
        assert np.array_equal(np.asarray(m1.x), np.asarray(m2.x))
        assert np.array_equal(np.asarray(m1.resnorm), np.asarray(m2.resnorm))

    def test_none_and_precond_chunks_cached_separately(self, lap, rng):
        """A preconditioned chunk must never be served from (or evict)
        the plain chunk's cache slot for the same operator."""
        from repro.solvers import BlockJacobiPreconditioner
        from repro.solvers import stepper
        A, Ad, n = lap
        op = make_operator(A)
        M = BlockJacobiPreconditioner(A, block_size=8)
        b = A.permute(rng.standard_normal((n, 2)).astype(np.float32))
        st_plain = cg_init(op, b, tol=1e-6, maxiter=50)
        st_plain = cg_step(op, st_plain, 10)
        st_pre = cg_init(op, b, tol=1e-6, maxiter=50, M=M)
        st_pre = cg_step(op, st_pre, 10, M=M)
        names = {k[0] for k in stepper._chunk_cache[op]}
        assert "cg" in names and "cg_precond" in names


class TestMergeColumns:
    def test_merge_restarts_selected_columns_only(self, lap, rng):
        A, Ad, n = lap
        op = make_operator(A)
        b = A.permute(rng.standard_normal((n, 3)).astype(np.float32))
        st = cg_init(op, b, tol=1e-7, maxiter=500)
        st = cg_step(op, st, 5)
        b2 = A.permute(rng.standard_normal((n, 3)).astype(np.float32))
        fresh = cg_init(op, b2, tol=1e-7, maxiter=500)
        merged = merge_columns(st, fresh, [1])
        # column 1 restarted, columns 0/2 untouched, counters preserved
        assert np.array_equal(np.asarray(merged.x[:, 1]),
                              np.asarray(fresh.x[:, 1]))
        for j in (0, 2):
            assert np.array_equal(np.asarray(merged.x[:, j]),
                                  np.asarray(st.x[:, j]))
            assert np.array_equal(np.asarray(merged.r[:, j]),
                                  np.asarray(st.r[:, j]))
        assert int(merged.it) == int(st.it)

    def test_merged_column_converges_like_standalone(self, lap, rng):
        """A column spliced into a running block solves its own system to
        the same tolerance as a standalone solve (column independence)."""
        A, Ad, n = lap
        op = make_operator(A)
        b = A.permute(rng.standard_normal((n, 2)).astype(np.float32))
        st = pipelined_cg_init(op, b, tol=1e-6, maxiter=400)
        st = pipelined_cg_step(op, st, 7)
        bnew = rng.standard_normal(n).astype(np.float32)
        b3 = np.asarray(b).copy()
        b3[:, 0] = np.asarray(A.permute(bnew))
        fresh = pipelined_cg_init(op, jnp.asarray(b3), tol=1e-6, maxiter=400)
        st = merge_columns(st, fresh, [0])
        st = pipelined_cg_step(op, st, 400)
        res = pipelined_cg_finalize(st)
        x0 = np.asarray(A.unpermute(res.x[:, 0]))
        assert bool(np.asarray(res.converged)[0])
        assert np.abs(Ad @ x0 - bnew).max() / np.abs(bnew).max() < 1e-3


class TestBlockKrylov:
    """block=True shares one Krylov space across the rhs block (ISSUE 9):
    width-1 delegates to the column stepper bit for bit, wider blocks
    converge to the same tolerance with coupled small-matrix recurrences,
    and chunked block composition stays bit-identical."""

    def test_width1_is_plain_stepper(self, lap, rng):
        """A 1-column block solve IS the column solve: same state type,
        bit-identical results."""
        from repro.solvers import CGState, MinresState
        A, Ad, n = lap
        op = make_operator(A)
        b = A.permute(rng.standard_normal((n, 1)).astype(np.float32))
        assert type(cg_init(op, b, block=True)) is CGState
        assert type(minres_init(op, b, block=True)) is MinresState
        ref = cg(op, b, tol=1e-6, maxiter=200)
        blk = cg(op, b, tol=1e-6, maxiter=200, block=True)
        assert np.array_equal(np.asarray(ref.x), np.asarray(blk.x))
        assert int(ref.iters) == int(blk.iters)
        mref = minres(op, b, tol=1e-6, maxiter=300)
        mblk = minres(op, b, tol=1e-6, maxiter=300, block=True)
        assert np.array_equal(np.asarray(mref.x), np.asarray(mblk.x))
        assert int(mref.iters) == int(mblk.iters)

    @pytest.mark.parametrize("dtype,tol,check", [
        (np.float32, 1e-5, 1e-3),
        (np.float64, 1e-9, 1e-7),
    ])
    def test_block_cg_converges(self, lap, rng, dtype, tol, check):
        """Block CG solves every column to tolerance in no more (usually
        fewer) iterations than column CG — the shared space absorbs each
        column's Krylov information."""
        from contextlib import nullcontext
        A, Ad, n = lap
        scope = nullcontext()
        if dtype == np.float64:
            scope = jax.enable_x64(True)
            r, c = np.nonzero(Ad)
            A = from_coo(r, c, Ad[r, c].astype(np.float64), (n, n), C=16,
                         sigma=32, w_align=4, dtype=np.float64)
        with scope:
            op = make_operator(A)
            b = A.permute(rng.standard_normal((n, 4)).astype(dtype))
            ref = cg(op, b, tol=tol, maxiter=400)
            blk = cg(op, b, tol=tol, maxiter=400, block=True)
            assert bool(np.all(np.asarray(blk.converged)))
            assert int(blk.iters) <= int(ref.iters)
            X = np.asarray(A.unpermute(blk.x))
            B = np.asarray(A.unpermute(b))
        rel = np.abs(Ad.astype(dtype) @ X - B).max() / np.abs(B).max()
        assert rel < check, rel

    def test_block_cg_complex64(self, rng):
        n = 48
        B = (rng.standard_normal((n, n))
             + 1j * rng.standard_normal((n, n)))
        H = (B @ B.conj().T + n * np.eye(n)).astype(np.complex64)
        r, c = np.nonzero(H)
        A = from_coo(r, c, H[r, c], (n, n), C=8, sigma=16,
                     dtype=np.complex64)
        op = make_operator(A)
        b = A.permute((rng.standard_normal((n, 3))
                       + 1j * rng.standard_normal((n, 3))
                       ).astype(np.complex64))
        blk = cg(op, b, tol=1e-5, maxiter=200, block=True)
        assert bool(np.all(np.asarray(blk.converged)))
        X = np.asarray(A.unpermute(blk.x))
        bb = np.asarray(A.unpermute(b))
        assert np.abs(H @ X - bb).max() / np.abs(bb).max() < 1e-3

    def test_block_minres_indefinite(self, rng):
        """Block MINRES on an indefinite matrix: fewer sweeps than column
        MINRES, honest residuals (resnorm matches the true residual)."""
        n = 96
        Q, _ = np.linalg.qr(rng.standard_normal((n, n)))
        ev = np.linspace(-2.0, 3.0, n)
        ev[np.abs(ev) < 0.1] = 0.1                # keep it invertible
        H = (Q * ev) @ Q.T
        H = ((H + H.T) / 2).astype(np.float32)
        r, c = np.nonzero(H)
        A = from_coo(r, c, H[r, c], (n, n), C=8, sigma=8, dtype=np.float32)
        op = make_operator(A)
        b = A.permute(rng.standard_normal((n, 4)).astype(np.float32))
        ref = minres(op, b, tol=1e-5, maxiter=400)
        blk = minres(op, b, tol=1e-5, maxiter=400, block=True)
        assert bool(np.all(np.asarray(blk.converged)))
        assert int(blk.iters) < int(ref.iters)
        X = np.asarray(A.unpermute(blk.x))
        B = np.asarray(A.unpermute(b))
        bn = np.linalg.norm(B, axis=0)
        true = np.linalg.norm(H @ X - B, axis=0)
        assert np.all(true / bn < 1e-4), true / bn
        # the carried recurrence residual tracks the true one
        np.testing.assert_allclose(np.asarray(blk.resnorm), true,
                                   rtol=0.5, atol=1e-6 * bn.max())

    @pytest.mark.parametrize("k", [1, 7, 100])
    def test_block_chunked_equals_monolithic(self, lap, rng, k):
        """Chunk boundaries never perturb the coupled recurrences: any
        chunk size reproduces the monolithic block solve bit for bit."""
        A, Ad, n = lap
        op = make_operator(A)
        b = A.permute(rng.standard_normal((n, 3)).astype(np.float32))
        st = cg_init(op, b, tol=1e-6, maxiter=100, block=True)
        st = cg_step(op, st, 200)                 # one chunk covers all
        st2 = cg_init(op, b, tol=1e-6, maxiter=100, block=True)
        for _ in range(100 // k + 1):
            st2 = cg_step(op, st2, k)
        assert np.array_equal(np.asarray(st.x), np.asarray(st2.x))
        assert int(st.it) == int(st2.it)
        m1 = minres_init(op, b, tol=1e-6, maxiter=100, block=True)
        m1 = minres_step(op, m1, 200)
        m2 = minres_init(op, b, tol=1e-6, maxiter=100, block=True)
        for _ in range(100 // k + 1):
            m2 = minres_step(op, m2, k)
        assert np.array_equal(np.asarray(m1.x), np.asarray(m2.x))
        assert int(m1.it) == int(m2.it)

    def test_rank_deficient_rhs_deflates(self, lap, rng):
        """Duplicate rhs columns make the block rank-deficient from step
        one; deflation must absorb that instead of dividing by zero."""
        A, Ad, n = lap
        op = make_operator(A)
        col = rng.standard_normal(n).astype(np.float32)
        b = np.stack([col, col, rng.standard_normal(n).astype(np.float32)],
                     axis=1)
        bp = A.permute(jnp.asarray(b))
        for solve in (cg, minres):
            res = solve(op, bp, tol=1e-5, maxiter=400, block=True)
            assert bool(np.all(np.asarray(res.converged))), solve.__name__
            X = np.asarray(A.unpermute(res.x))
            rel = (np.abs(Ad @ X - b).max() / np.abs(b).max())
            assert rel < 1e-3, (solve.__name__, rel)
            # the duplicate columns get the same answer
            np.testing.assert_allclose(X[:, 0], X[:, 1], atol=1e-4)

    def test_zero_rhs_column_done_at_init(self, lap, rng):
        """A zero rhs column converges immediately with x = 0 in every
        stepper (tol^2 * ||b||^2 = 0 used to be unreachable)."""
        from repro.solvers import pipelined_cg_finalize
        A, Ad, n = lap
        op = make_operator(A)
        b = np.zeros((n, 2), np.float32)
        b[:, 1] = rng.standard_normal(n)
        bp = A.permute(jnp.asarray(b))
        x0 = A.permute(rng.standard_normal((n, 2)).astype(np.float32))
        for init, fin in ((cg_init, cg_finalize),
                          (minres_init, minres_finalize),
                          (pipelined_cg_init, pipelined_cg_finalize)):
            st = init(op, bp, x0, tol=1e-8, maxiter=100)
            assert bool(np.asarray(st.done)[0]), init.__name__
            res = fin(st)
            assert np.abs(np.asarray(res.x)[:, 0]).max() == 0.0
        # and in block mode, where the zero column deflates
        for init in (lambda *a, **k: cg_init(*a, block=True, **k),
                     lambda *a, **k: minres_init(*a, block=True, **k)):
            st = init(op, bp, tol=1e-8, maxiter=100)
            assert bool(np.asarray(st.done)[0])

    def test_block_states_refuse_column_merge(self, lap, rng):
        """The carried (b, b) Gram blocks couple every column; splicing
        must fail loudly (the service warm-restarts instead)."""
        A, Ad, n = lap
        op = make_operator(A)
        b = A.permute(rng.standard_normal((n, 3)).astype(np.float32))
        st = cg_init(op, b, tol=1e-6, maxiter=100, block=True)
        fresh = cg_init(op, b, tol=1e-6, maxiter=100, block=True)
        with pytest.raises(ValueError, match="column-spliced"):
            merge_columns(st, fresh, [1])
        mst = minres_init(op, b, tol=1e-6, maxiter=100, block=True)
        with pytest.raises(ValueError, match="column-spliced"):
            merge_columns(mst, mst, [0])

    def test_block_with_precond_raises(self, lap, rng):
        from repro.solvers import BlockJacobiPreconditioner
        A, Ad, n = lap
        op = make_operator(A)
        M = BlockJacobiPreconditioner(A, block_size=8)
        b = A.permute(rng.standard_normal((n, 2)).astype(np.float32))
        with pytest.raises(NotImplementedError, match="block=True"):
            cg_init(op, b, M=M, block=True)
        with pytest.raises(NotImplementedError, match="block=True"):
            minres_init(op, b, M=M, block=True)
        with pytest.raises(NotImplementedError, match="block=True"):
            pipelined_cg_init(op, b, block=True)


class TestMatrixFreeFusedDots:
    def test_dots_match_ghost_operator(self, lap, rng):
        """Swapping in a matrix-free operator must not change solver
        numerics: the fused dots use the same widened/compensated
        accumulation as the SELL-C-sigma reference path."""
        A, Ad, n = lap
        ghost = make_operator(A)
        free = MatrixFreeOperator(lambda x: ghost.mv(x), ghost.n, np.float32)
        x = A.permute(rng.standard_normal((n, 3)).astype(np.float32))
        opts = SpmvOpts(dot_yy=True, dot_xy=True, dot_xx=True)
        _, _, d_ghost = ghost.mv_fused(x, opts=opts)
        _, _, d_free = free.mv_fused(x, opts=opts)
        assert d_free.dtype == d_ghost.dtype
        np.testing.assert_array_equal(np.asarray(d_ghost), np.asarray(d_free))

    def test_dots_conjugate_for_complex(self, rng):
        n = 64
        H = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
        H = ((H + H.conj().T) / 2).astype(np.complex64)
        op = MatrixFreeOperator(lambda x: jnp.asarray(H) @ x, n, np.complex64)
        x = (rng.standard_normal((n, 1))
             + 1j * rng.standard_normal((n, 1))).astype(np.complex64)
        _, _, dots = op.mv_fused(jnp.asarray(x), opts=SpmvOpts(dot_xx=True))
        # <x, x> must be conjugated: real, positive, == ||x||^2
        expect = np.sum(np.abs(x[:, 0]) ** 2)
        got = np.asarray(dots)[2, 0]
        assert abs(got.imag) < 1e-4 * expect
        np.testing.assert_allclose(got.real, expect, rtol=1e-5)

    def test_chain_axpby_without_z_raises(self, lap, rng):
        A, Ad, n = lap
        ghost = make_operator(A)
        free = MatrixFreeOperator(lambda x: ghost.mv(x), ghost.n, np.float32)
        x = A.permute(rng.standard_normal((n, 2)).astype(np.float32))
        with pytest.raises(ValueError, match="chained AXPBY"):
            free.mv_fused(x, opts=SpmvOpts(delta=0.5, eta=1.0))
