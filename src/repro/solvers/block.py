"""True block-Krylov steppers on the tall-skinny GEMM kernels.

The SolverService packs independent right-hand sides into width-``b``
column blocks, but the column-independent ``cg``/``minres`` steppers
treat that block as a batching trick.  This module is the block
*method*: all columns share **one Krylov space per block**, so every
iteration costs one block SpMV sweep for the whole batch and the
remaining work is tall-skinny dense algebra — Gram matrices ``Vᴴ·W``
through the Kahan-compensated :func:`repro.kernels.ops.tsmttsm` kernel
and basis updates ``V·X`` through :func:`repro.kernels.ops.tsmm`
(the paper's §5.2–5.3 case for row-major block vectors; Kreutzer et
al.'s KPM work shows the node-level win).

* **Block CG** (O'Leary 1980): the step/projection coefficients become
  small ``(b, b)`` systems ``α = (PᴴAP)⁻¹(RᴴR)`` and
  ``β = S_old⁻¹ S_new`` solved by Cholesky with an eigh-pinv fallback —
  clipped eigenvalues *are* the deflation of rank-deficient search
  directions.
* **Block MINRES**: block Lanczos with SVQB orthonormalization of the
  candidate block (Stathopoulos & Wu 2002) and an incremental band QR
  of the block tridiagonal via ``2b×2b`` orthogonal reflections — the
  block generalization of MINRES' Givens recurrence.

Converged columns are **deflated, not dropped**: their residual columns
are masked to zero inside the shared space and the small systems carry
an identity block on their indices, so the live columns keep iterating
in a thinner effective space while the block shape (and the compiled
chunk program) stays fixed.  That is what lets the service's
retire/refill machinery treat block batches like any other batch.

States are stepper-shaped (``it``/``maxiter``/``done`` fields) so
:func:`repro.solvers.stepper.run_chunk` drives them unchanged, and the
field names ``x``/``rr``/``resn`` line up with ``cg_finalize`` /
``minres_finalize``.  Because the carried ``(b, b)`` Gram/reflection
blocks couple all columns, these states can **not** be column-spliced
by ``merge_columns_masked`` — the service refills block batches with a
warm restart instead (see ``runtime/service.py``).

Entry points are not public API: use ``cg(..., block=True)`` /
``minres(..., block=True)`` or ``SolverService.submit(..., block=True)``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.spmv import as2d
from repro.kernels import ops

__all__ = ["BlockCGState", "BlockMinresState",
           "block_cg_init", "block_minres_init",
           "block_cg_body", "block_minres_body"]


# ------------------------------------------------------------- small helpers
def _mm(a, b):
    """``a @ b`` at full precision: a TPU otherwise runs an f32 matmul as
    one bfloat16 pass, which costs block CG its accuracy."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _colsum(v):
    """Per-column squared norm, always real (matches cg._colsum)."""
    if jnp.iscomplexobj(v):
        return jnp.sum((jnp.conj(v) * v).real, axis=0)
    return jnp.sum(v * v, axis=0)


def _mask_cols(v, done):
    """Zero the converged columns of a block vector (deflation mask)."""
    return jnp.where(done[None, :], jnp.zeros((), v.dtype), v)


def _done_eye(done, dtype):
    """Identity block on the converged indices: keeps the small Gram
    systems nonsingular and — because masked columns make the
    cross-terms exactly zero — decoupled from the live columns."""
    return jnp.diag(done.astype(dtype))


def _gram(V, W):
    """``VᴴW`` through the Kahan-compensated tall-skinny kernel."""
    return ops.tsmttsm(V, W, kahan=True)


def _diag_real(G):
    d = jnp.diagonal(G)
    return d.real if jnp.iscomplexobj(d) else d


def _herm(G):
    return 0.5 * (G + jnp.conj(G.T))


def _eigh_pinv_apply(G, B, *, rel_eps):
    """``G⁺ B`` with eigenvalues below ``rel_eps * λ_max`` clipped to a
    zero inverse — rank-deficient directions receive zero weight (the
    deflation half of the Cholesky fallback)."""
    w, U = jnp.linalg.eigh(_herm(G))
    wmax = jnp.maximum(jnp.max(jnp.abs(w)), jnp.finfo(w.dtype).tiny)
    inv = jnp.where(w > rel_eps * wmax, 1.0 / jnp.where(w == 0, 1.0, w), 0.0)
    return _mm(U, inv[:, None] * _mm(jnp.conj(U.T), B))


def _spd_solve(G, B):
    """Solve ``G X = B`` for Hermitian positive semidefinite ``G``.

    Cholesky first (the common well-conditioned case); if the factor or
    the solve is non-finite, a clipped eigh pseudo-inverse takes over —
    both branches are computed under jit, ``jnp.where`` selects.
    """
    L = jnp.linalg.cholesky(G)
    sol_c = jax.scipy.linalg.cho_solve((L, True), B)
    ok = jnp.all(jnp.isfinite(sol_c))
    m = G.shape[0]
    rel_eps = jnp.finfo(_diag_real(G).dtype).eps * m
    sol_e = _eigh_pinv_apply(G, B, rel_eps=rel_eps)
    return jnp.where(ok, sol_c, sol_e)


def _svqb(W, *, rel_eps):
    """SVQB orthonormalization: ``W = V B`` with ``VᴴV ≈ I``.

    Gram through the compensated tsmttsm kernel, eigendecomposition of
    the scaled Gram, basis update through tsmm.  Eigenvalues below
    ``rel_eps * λ_max`` are clipped: the corresponding directions are
    deflated (zero columns in ``V``, zero rows in ``B``), which is how
    a rank-deficient Lanczos candidate block sheds exhausted directions
    without changing the block shape.  A fully zero ``W`` yields
    ``V = 0``, ``B = 0`` (happy breakdown).
    """
    G = _gram(W, W)                               # (m, m) Hermitian PSD
    d = _diag_real(G)
    ds = jnp.where(d <= 0, 1.0, d) ** -0.5        # Jacobi scaling
    dsc = ds.astype(G.dtype)
    Gs = _herm(dsc[:, None] * G * dsc[None, :])
    w, U = jnp.linalg.eigh(Gs)
    wmax = jnp.max(jnp.abs(w))
    keep = w > rel_eps * jnp.maximum(wmax, jnp.finfo(w.dtype).tiny)
    inv_sqrt = jnp.where(keep, jnp.where(w == 0, 1.0, w) ** -0.5, 0.0)
    sqrt_w = jnp.where(keep, jnp.sqrt(jnp.abs(w)), 0.0)
    T = (dsc[:, None] * U) * inv_sqrt[None, :].astype(G.dtype)
    V = ops.tsmm(W, T)                            # orthonormal basis
    B = (sqrt_w[:, None].astype(G.dtype) * jnp.conj(U.T)
         * (1.0 / dsc)[None, :])                  # W ≈ V B
    return V, B


def _rel_eps(dtype, m):
    import numpy as np
    return float(np.finfo(np.dtype(jnp.zeros((), dtype).real.dtype)).eps) * m


# ------------------------------------------------------------------ block CG
class BlockCGState(NamedTuple):
    """Resumable block-CG state (one shared Krylov space per block).

    Dubrulle's residual-orthonormalized variant (BCGrQ): the residual
    block is carried in factored form ``R_k = V_k C_k`` with ``V_k``
    SVQB-orthonormal and ``C_k`` a cumulative ``(b, b)`` triangular-ish
    coefficient — re-orthonormalizing every step is what keeps f32
    blocks from stalling on ill-conditioned operators (vanilla O'Leary
    loses conjugacy).  The ``(b, b)`` carry couples the columns, which
    is why this state cannot be column-spliced (the service
    warm-restarts instead).  ``x``/``rr``/``it``/``done`` line up with
    :class:`repro.solvers.cg.CGState` so ``cg_finalize`` and the
    service's retire bookkeeping work unchanged.
    """

    x: jax.Array              # (n, b) iterate
    v: jax.Array              # (n, b) orthonormal residual basis V_k
    p: jax.Array              # (n, b) scaled search-direction block P~_k
    cmat: jax.Array           # (b, b) cumulative coefficient C_k (R = V C)
    rr: jax.Array             # (b,)   true ||r||^2 (real)
    tol2: jax.Array           # (b,)   per-column squared abs tolerance
    it: jax.Array             # ()     block iteration counter
    maxiter: jax.Array        # ()     block iteration cap
    done: jax.Array           # (b,)   per-column convergence flag


# block states must never be column-spliced: the (b, b) carries couple
# every column (see merge_columns_masked's guard)
BlockCGState.BLOCK_COUPLED = True


def _tol2_floored(tol, b2):
    """Squared relative tolerance with the zero-rhs floor (matches the
    fixed ``cg._tol2`` semantics: a zero column must not yield 0)."""
    tiny = jnp.finfo(b2.dtype).tiny
    bnorm2 = jnp.maximum(_colsum(b2), tiny)
    t = jnp.broadcast_to(jnp.asarray(tol, bnorm2.dtype), bnorm2.shape)
    return jnp.maximum((t * t) * bnorm2, tiny)


def _start_block(op, b, x0):
    """Shared init plumbing: 2-d views, zero-rhs columns solved by
    ``x = 0`` immediately (their residual is then exactly zero)."""
    b2, _ = as2d(b)
    x = jnp.zeros_like(b2) if x0 is None else as2d(x0)[0]
    bzero = _colsum(b2) <= 0
    x = _mask_cols(x, bzero)
    r = b2 - op.mv(x)
    return b2, x, r


def block_cg_init(op, b: jax.Array, x0: Optional[jax.Array] = None, *,
                  tol=1e-8, maxiter: int = 500) -> BlockCGState:
    """Initial block-CG state (op must be SPD; all columns share one
    Krylov space).  ``tol`` may be a scalar or per-column ``(b,)``."""
    b2, x, r = _start_block(op, b, x0)
    tol2 = _tol2_floored(tol, b2)
    V, C = _svqb(r, rel_eps=_rel_eps(r.dtype, b2.shape[1]))
    rr = _colsum(C)                                # ||R e_j||^2 = ||C e_j||^2
    done = rr <= tol2
    return BlockCGState(x=x, v=V, p=V, cmat=C, rr=rr, tol2=tol2,
                        it=jnp.asarray(0),
                        maxiter=jnp.asarray(int(maxiter)), done=done)


def block_cg_body(op, st: BlockCGState) -> BlockCGState:
    """One block-CG iteration (Dubrulle's BCGrQ): one block SpMV, two
    compensated Grams (step Gram + SVQB), three tall-skinny updates, one
    ``(b, b)`` SPD solve.

    With ``R_k = V_k C_k`` the O'Leary recurrences collapse to the
    orthonormal-basis form: ``γ = (P~ᴴAP~)⁻¹``, ``X += P~ (γ C)``,
    ``V_{k+1} ρ = V_k − (AP~) γ`` (SVQB), ``C_{k+1} = ρ C_k``,
    ``P~_{k+1} = V_{k+1} + P~ ρᴴ``.  SVQB's eigenvalue clipping deflates
    exhausted directions (zero columns in ``V``, zero rows in ``ρ``) and
    the Cholesky→eigh-pinv fallback in ``γ`` gives them zero weight, so
    a rank-deficient block keeps iterating in a thinner space."""
    dn = st.done
    m = st.cmat.shape[0]
    rel = _rel_eps(st.v.dtype, m)
    T = op.mv(st.p)                                # one sweep for the block
    G = _herm(_gram(st.p, T))                      # P~ᴴAP~
    gamma = _spd_solve(G, jnp.eye(m, dtype=G.dtype))
    upd = _mm(gamma, st.cmat)                      # γ C — per-column steps
    upd = jnp.where(dn[None, :], jnp.zeros((), upd.dtype), upd)
    x = ops.tsmm(st.p, upd, st.x, 1.0, 1.0)        # X += P~ (γ C)
    W = ops.tsmm(T, gamma, st.v, -1.0, 1.0)        # V − (AP~) γ
    Vn, rho = _svqb(W, rel_eps=rel)
    cn = _mm(rho, st.cmat)                         # C_{k+1} = ρ C_k
    rr_new = jnp.where(dn, st.rr, _colsum(cn).astype(st.rr.dtype))
    p = ops.tsmm(st.p, jnp.conj(rho.T), Vn, 1.0, 1.0)  # P~' = V' + P~ ρᴴ
    return BlockCGState(x=x, v=Vn, p=p, cmat=cn, rr=rr_new, tol2=st.tol2,
                        it=st.it + 1, maxiter=st.maxiter,
                        done=dn | (rr_new <= st.tol2))


# -------------------------------------------------------------- block MINRES
class BlockMinresState(NamedTuple):
    """Resumable block-MINRES state (block Lanczos + incremental band QR).

    The Lanczos space is shared by every column; the scalar Givens
    cosines/sines of column MINRES become carried ``(b, b)`` blocks of
    the last two orthogonal reflections (``ta``..``td``, ``tb_old``,
    ``td_old``), the rotated rhs ``eta`` becomes the ``(b, b)`` carry
    ``h``, and the per-column residual estimate is the column norm of
    the rejected part ``h_next``.  ``x``/``resn``/``it``/``done`` line
    up with :class:`repro.solvers.minres.MinresState` so
    ``minres_finalize`` works unchanged.
    """

    x: jax.Array              # (n, b) iterate
    v: jax.Array              # (n, b) current Lanczos block V_j
    v_old: jax.Array          # (n, b) V_{j-1}
    w: jax.Array              # (n, b) update-direction block W_j
    w_old: jax.Array          # (n, b) W_{j-1}
    cmat: jax.Array           # (b, b) subdiagonal block C_{j-1}
    ta: jax.Array             # (b, b) reflection blocks of step j-1 ...
    tb: jax.Array
    tc: jax.Array
    td: jax.Array
    tb_old: jax.Array         # (b, b) ... and of step j-2
    td_old: jax.Array
    h: jax.Array              # (b, b) rotated rhs carry
    resn: jax.Array           # (b,)   residual-norm estimate
    tolb: jax.Array           # (b,)   per-column absolute tolerance
    it: jax.Array             # ()
    maxiter: jax.Array        # ()
    done: jax.Array           # (b,)


BlockMinresState.BLOCK_COUPLED = True


def block_minres_init(op, b: jax.Array, x0: Optional[jax.Array] = None, *,
                      tol=1e-8, maxiter: int = 500) -> BlockMinresState:
    """Initial block-MINRES state (op symmetric/Hermitian, possibly
    indefinite).  ``tol`` may be a scalar or per-column ``(b,)``."""
    b2, x, r = _start_block(op, b, x0)
    m = b2.shape[1]
    tiny = jnp.finfo(b2.dtype).tiny
    bnorm = jnp.sqrt(jnp.maximum(_colsum(b2), tiny))
    tolb = jnp.maximum(
        jnp.broadcast_to(jnp.asarray(tol, bnorm.dtype), bnorm.shape) * bnorm,
        tiny)
    V1, B0 = _svqb(r, rel_eps=_rel_eps(r.dtype, m))
    resn = jnp.sqrt(_colsum(B0))                   # true ||r_j|| column-wise
    done = resn <= tolb
    zeros = jnp.zeros_like(b2)
    eye = jnp.eye(m, dtype=B0.dtype)
    zb = jnp.zeros_like(eye)
    return BlockMinresState(
        x=x, v=V1, v_old=zeros, w=zeros, w_old=zeros,
        cmat=zb, ta=eye, tb=zb, tc=zb, td=eye, tb_old=zb, td_old=eye,
        h=B0, resn=resn, tolb=tolb,
        it=jnp.asarray(0), maxiter=jnp.asarray(int(maxiter)), done=done)


def block_minres_body(op, st: BlockMinresState) -> BlockMinresState:
    """One block-MINRES iteration: block Lanczos step (SVQB-orthonormal
    candidate), the new block column of T pushed through the two carried
    reflections, one fresh ``2b×2b`` reflection from a complete QR, and
    the tall-skinny update of the direction block and iterate."""
    m = st.h.shape[0]
    rel = _rel_eps(st.v.dtype, m)
    Q = op.mv(st.v)                                # one sweep for the block
    Aj = _herm(_gram(st.v, Q))                     # diagonal block T_jj
    U = (Q - ops.tsmm(st.v, Aj)
         - ops.tsmm(st.v_old, jnp.conj(st.cmat.T)))
    # local reorthogonalization (second classical Gram-Schmidt pass
    # against the two in-band blocks): without it the f32 block Lanczos
    # basis drifts and the residual stalls an order above tol.  The
    # V_j correction folds into the diagonal block to keep T consistent.
    Ac = _gram(st.v, U)
    U = U - ops.tsmm(st.v, Ac)
    Aj = _herm(Aj + Ac)
    U = U - ops.tsmm(st.v_old, _gram(st.v_old, U))
    Vn, Cj = _svqb(U, rel_eps=rel)                 # U = V_{j+1} C_j

    # band column j of T through the two carried reflections
    CprevH = jnp.conj(st.cmat.T)
    tmp = _mm(st.td_old, CprevH)
    R3 = _mm(st.tb_old, CprevH)
    R2 = _mm(st.ta, tmp) + _mm(st.tb, Aj)
    d = _mm(st.tc, tmp) + _mm(st.td, Aj)
    # fresh reflection annihilating C_j under d (block Givens)
    M2 = jnp.concatenate([d, Cj], axis=0)          # (2b, b)
    Qc, Rfull = jnp.linalg.qr(M2, mode="complete")
    R1 = Rfull[:m]
    QH = jnp.conj(Qc.T)
    ta_n, tb_n = QH[:m, :m], QH[:m, m:]
    tc_n, td_n = QH[m:, :m], QH[m:, m:]
    h_keep = _mm(ta_n, st.h)
    h_next = _mm(tc_n, st.h)

    # W_j = (V_j - W_{j-1} R2 - W_{j-2} R3) R1^{-1}; a rank-deficient R1
    # (exhausted directions) gets unit diagonal stand-ins — their h_keep
    # weight is zero because the QR put nothing on those rows
    dg = _diag_real(R1)
    good = jnp.abs(dg) > rel * jnp.maximum(jnp.max(jnp.abs(dg)),
                                           jnp.finfo(dg.dtype).tiny)
    R1s = R1 + jnp.diag(jnp.where(good, 0.0, 1.0).astype(R1.dtype))
    R1inv = jax.scipy.linalg.solve_triangular(
        R1s, jnp.eye(m, dtype=R1.dtype), lower=False)
    R1inv = jnp.where(good[:, None] & good[None, :], R1inv,
                      jnp.zeros((), R1inv.dtype))
    cand = st.v - ops.tsmm(st.w, R2) - ops.tsmm(st.w_old, R3)
    Wn = ops.tsmm(cand, R1inv)

    upd = jnp.where(st.done[None, :], jnp.zeros((), st.h.dtype), h_keep)
    x = ops.tsmm(Wn, upd, st.x, 1.0, 1.0)          # X += W_j (kept rhs part)
    resn_col = jnp.sqrt(_colsum(h_next))
    resn = jnp.where(st.done, st.resn, resn_col.astype(st.resn.dtype))
    return BlockMinresState(
        x=x, v=Vn, v_old=st.v, w=Wn, w_old=st.w,
        cmat=Cj, ta=ta_n, tb=tb_n, tc=tc_n, td=td_n,
        tb_old=st.tb, td_old=st.td, h=h_next,
        resn=resn, tolb=st.tolb,
        it=st.it + 1, maxiter=st.maxiter,
        done=st.done | (resn <= st.tolb))
