"""Conjugate Gradient solvers on GHOST building blocks.

* ``cg``: (block) CG for SPD systems, one system per block-vector column
  (multiple right-hand sides).  Uses the paper's fusion features: the
  matvec is chained with the <p, Ap> dot (GHOST_SPMV_DOT_XY) — the
  communication/memory structure of the paper's augmented SpMV (C3).
* ``pipelined_cg``: Ghysels & Vanroose pipelined CG (paper section 1.1,
  category "hide communication"): the reduction bundle of an iteration is
  independent of the matvec ``q = A w``, so the two can overlap — exactly
  the dependency structure GHOST tasks were built to exploit (C5).

Both solvers are **resumable steppers**: ``cg_init`` builds a
:class:`CGState`, ``cg_step`` advances it by a jitted k-iteration chunk
(per-column ``done`` carried in the state), ``cg_finalize`` reads out a
:class:`CGResult`.  The classic entry points are thin compositions of the
three and bit-identical to a single monolithic solve; the chunked form is
what :class:`repro.runtime.service.SolverService` drives for continuous
batching (retire converged columns between chunks, refill from a queue).

``cg`` accepts an optional SPD preconditioner ``M`` (see
:mod:`repro.solvers.precond`): ``M=None`` runs the *exact* PR-3 state and
body — bit-identical, pinned in ``tests/test_steppers.py`` — while a
preconditioner switches to the :class:`PrecondCGState` stepper whose
``z = M r`` recurrence rides in the state.  Convergence is still tested
on the true residual ``||r||`` with the same per-column ``done``/``tol``
semantics, so the service's retire/refill logic is oblivious to ``M``.
``pipelined_cg`` is unpreconditioned and raises on ``M`` (the Ghysels &
Vanroose preconditioned variant needs an extra carry, not yet built).

Vectors are ``(n, b)`` in operator (permuted) space.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.spmv import SpmvOpts, as2d
from repro.core.trace import scope
from repro.solvers.block import BlockCGState, block_cg_body, block_cg_init
from repro.solvers.stepper import run_chunk


class CGResult(NamedTuple):
    x: jax.Array
    iters: jax.Array          # total iteration count
    resnorm: jax.Array        # per-column final ||r||
    converged: jax.Array      # per-column bool


class CGState(NamedTuple):
    """Resumable block-CG state (columns = independent systems)."""

    x: jax.Array              # (n, b) iterate
    r: jax.Array              # (n, b) residual
    p: jax.Array              # (n, b) search direction
    rr: jax.Array             # (b,)   <r, r> recurrence
    tol2: jax.Array           # (b,)   per-column squared abs tolerance
    it: jax.Array             # ()     block iteration counter
    maxiter: jax.Array        # ()     block iteration cap
    done: jax.Array           # (b,)   per-column convergence flag


class PrecondCGState(NamedTuple):
    """Resumable preconditioned block-CG state (``z = M r`` recurrence).

    ``rr`` (true squared residual norm, always real) drives the
    ``done``/``tol`` test exactly like plain CG; ``rz = <r, z>`` is the
    PCG recurrence scalar.  Column layout matches :class:`CGState`, so
    :func:`repro.solvers.stepper.merge_columns_masked` splices refills
    identically.
    """

    x: jax.Array              # (n, b) iterate
    r: jax.Array              # (n, b) residual
    z: jax.Array              # (n, b) preconditioned residual M r
    p: jax.Array              # (n, b) search direction
    rz: jax.Array             # (b,)   <r, z> recurrence
    rr: jax.Array             # (b,)   true ||r||^2 (real)
    tol2: jax.Array           # (b,)   per-column squared abs tolerance
    it: jax.Array             # ()     block iteration counter
    maxiter: jax.Array        # ()     block iteration cap
    done: jax.Array           # (b,)   per-column convergence flag


class PCGState(NamedTuple):
    """Resumable pipelined-CG state (Ghysels & Vanroose carries)."""

    x: jax.Array
    r: jax.Array
    w: jax.Array
    z: jax.Array
    s: jax.Array
    p: jax.Array
    gamma_prev: jax.Array     # (b,)
    alpha_prev: jax.Array     # (b,)
    tol2: jax.Array           # (b,)
    fresh: jax.Array          # (b,)  column has not taken its first step yet
    it: jax.Array             # ()
    maxiter: jax.Array        # ()
    done: jax.Array           # (b,)


def _colsum(v):
    """Per-column squared norm, always real.

    The complex branch is a trace-time Python switch: the real-dtype
    expression is character-identical to PR 3, preserving the pinned
    bit-identity of every real solve.
    """
    with scope("dots"):
        if jnp.iscomplexobj(v):
            return jnp.sum((jnp.conj(v) * v).real, axis=0)
        return jnp.sum(v * v, axis=0)


def _inner(a, b):
    """Per-column <a, b> with the conjugate-linear first argument."""
    with scope("dots"):
        if jnp.iscomplexobj(a) or jnp.iscomplexobj(b):
            return jnp.sum(jnp.conj(a) * b, axis=0)
        return jnp.sum(a * b, axis=0)


def _maybe_1d(res: CGResult, was1d: bool) -> CGResult:
    if not was1d:
        return res
    return CGResult(res.x[:, 0], res.iters, res.resnorm[0], res.converged[0])


def _tol2(tol, bnorm2):
    """Squared relative tolerance, per column (``tol`` scalar or (b,)).

    Floored at ``tiny``: a (near-)zero rhs column would otherwise yield
    ``tol2 = 0`` — a threshold only an exactly-zero residual can meet —
    and stall its whole service block until maxiter.
    """
    t = jnp.broadcast_to(jnp.asarray(tol, bnorm2.dtype), bnorm2.shape)
    return jnp.maximum((t * t) * bnorm2, jnp.finfo(bnorm2.dtype).tiny)


# ------------------------------------------------------------------ plain CG
def cg_init(op, b: jax.Array, x0: Optional[jax.Array] = None, *,
            tol=1e-8, maxiter: int = 500, M=None, block: bool = False):
    """Initial stepper state.  ``tol`` may be a scalar or per-column (b,).

    ``M=None`` returns the plain :class:`CGState` (the unchanged PR-3
    path); an SPD preconditioner (``M.apply(r)`` in operator space, see
    :mod:`repro.solvers.precond`) returns a :class:`PrecondCGState`.

    ``block=True`` returns a :class:`repro.solvers.block.BlockCGState`
    whose columns share **one Krylov space** (Gram matrices through the
    compensated tsmttsm kernel, updates through tsmm) — fewer SpMV
    sweeps per converged column on multi-rhs workloads.  A one-column
    rhs delegates to the plain stepper (trivially bit-identical), and
    ``block=True`` with a preconditioner is not implemented.
    """
    b2, _ = as2d(b)
    if block and b2.shape[1] > 1:
        if M is not None:
            raise NotImplementedError(
                "cg(block=True) does not support preconditioning yet; "
                "drop M or use the column-wise block=False stepper")
        return block_cg_init(op, b2, x0, tol=tol, maxiter=maxiter)
    x = jnp.zeros_like(b2) if x0 is None else as2d(x0)[0]
    bnorm2 = _colsum(b2)
    # zero-rhs columns are solved by x = 0 on the spot: any relative
    # tolerance is met by the exact solution, and the zeroed iterate
    # makes the residual exactly zero so done is set at init
    bzero = bnorm2 <= 0
    x = jnp.where(bzero[None, :], jnp.zeros((), b2.dtype), x)
    r = b2 - op.mv(x)
    rr = _colsum(r)
    bnorm2 = jnp.maximum(bnorm2, jnp.finfo(b2.dtype).tiny)
    tol2 = _tol2(tol, bnorm2)
    if M is None:
        return CGState(x=x, r=r, p=r, rr=rr, tol2=tol2,
                       it=jnp.asarray(0), maxiter=jnp.asarray(int(maxiter)),
                       done=rr <= tol2)
    z = M.apply(r)
    return PrecondCGState(x=x, r=r, z=z, p=z, rz=_inner(r, z), rr=rr,
                          tol2=tol2, it=jnp.asarray(0),
                          maxiter=jnp.asarray(int(maxiter)),
                          done=rr <= tol2)


def _cg_body(op, st: CGState) -> CGState:
    # fused: q = A p and <p, q> in one sweep (GHOST_SPMV_DOT_XY)
    q, _, dots = op.mv_fused(st.p, opts=SpmvOpts(dot_xy=True))
    # dots may accumulate wider than the vectors (f64 under x64);
    # cast the recurrence scalar back so the loop carry stays stable.
    # rr is always real; for Hermitian PD complex operators <p, Ap> is
    # real up to rounding — take .real explicitly (complex->real astype
    # is deprecated), a no-op branch for real dtypes
    pq = dots[1]
    if jnp.iscomplexobj(pq):
        pq = pq.real
    pq = pq.astype(st.rr.dtype)
    alpha = jnp.where(st.done, 0.0, st.rr / jnp.where(pq == 0, 1.0, pq))
    x = st.x + alpha[None, :] * st.p
    r = st.r - alpha[None, :] * q
    rr_new = _colsum(r)
    beta = rr_new / jnp.where(st.rr == 0, 1.0, st.rr)
    p = jnp.where(st.done[None, :], st.p, r + beta[None, :] * st.p)
    return CGState(x=x, r=r, p=p, rr=rr_new, tol2=st.tol2,
                   it=st.it + 1, maxiter=st.maxiter,
                   done=st.done | (rr_new <= st.tol2))


def _cg_precond_body(op, M, st: PrecondCGState) -> PrecondCGState:
    # fused: q = A p and <p, q> in one sweep (GHOST_SPMV_DOT_XY)
    q, _, dots = op.mv_fused(st.p, opts=SpmvOpts(dot_xy=True))
    pq = dots[1].astype(st.rz.dtype)
    alpha = jnp.where(st.done, 0.0, st.rz / jnp.where(pq == 0, 1.0, pq))
    x = st.x + alpha[None, :] * st.p
    r = st.r - alpha[None, :] * q
    rr_new = _colsum(r)
    z = M.apply(r)
    rz_new = _inner(r, z)
    beta = rz_new / jnp.where(st.rz == 0, 1.0, st.rz)
    p = jnp.where(st.done[None, :], st.p, z + beta[None, :] * st.p)
    return PrecondCGState(x=x, r=r, z=z, p=p, rz=rz_new, rr=rr_new,
                          tol2=st.tol2, it=st.it + 1, maxiter=st.maxiter,
                          done=st.done | (rr_new <= st.tol2))


def cg_step(op, state, k: int, M=None):
    """Advance up to ``k`` iterations (jitted chunk, early-exits when all
    columns are done or ``maxiter`` is reached).  Pass the same ``M`` the
    state was initialized with (``None`` for a plain :class:`CGState`)."""
    if isinstance(state, BlockCGState):
        if M is not None:
            raise ValueError("block CG states are unpreconditioned; "
                             "M must be None")
        return run_chunk(op, "block_cg", k, state, block_cg_body)
    if M is None:
        if isinstance(state, PrecondCGState):
            raise ValueError("state was initialized with a preconditioner; "
                             "pass the same M to cg_step")
        return run_chunk(op, "cg", k, state, _cg_body)
    if not isinstance(state, PrecondCGState):
        raise ValueError("state was initialized without a preconditioner; "
                         "call cg_init(..., M=M) first")
    return run_chunk(op, "cg_precond", k, state,
                     lambda o, s: _cg_precond_body(o, M, s), extra_key=M)


def cg_finalize(state) -> CGResult:
    return CGResult(state.x, state.it, jnp.sqrt(state.rr), state.done)


def cg(op, b: jax.Array, x0: Optional[jax.Array] = None, *,
       tol: float = 1e-8, maxiter: int = 500, M=None,
       block: bool = False) -> CGResult:
    """Block (P)CG.  op must be SPD; ``M`` too.  ``block=False`` solves
    the columns independently; ``block=True`` shares one Krylov space
    across them (see :func:`cg_init`)."""
    was1d = b.ndim == 1
    state = cg_init(op, b, x0, tol=tol, maxiter=maxiter, M=M, block=block)
    state = cg_step(op, state, maxiter, M=M)
    return _maybe_1d(cg_finalize(state), was1d)


# -------------------------------------------------------------- pipelined CG
def _no_pipelined_precond(M) -> None:
    if M is not None:
        raise NotImplementedError(
            "pipelined_cg does not support preconditioning: the Ghysels & "
            "Vanroose preconditioned variant needs an extra u = M r carry "
            "that this stepper does not yet implement.  Use cg(..., M=M) "
            "(preconditioned CG) or drop the preconditioner.")


def pipelined_cg_init(op, b: jax.Array, x0: Optional[jax.Array] = None, *,
                      tol=1e-8, maxiter: int = 500, M=None,
                      block: bool = False) -> PCGState:
    """Initial pipelined-CG stepper state.

    ``M`` is accepted for signature parity with :func:`cg_init` only and
    must be ``None``: any preconditioner raises
    :class:`NotImplementedError` here (and in ``pipelined_cg_step`` /
    ``pipelined_cg``) — the Ghysels & Vanroose preconditioned variant
    needs an extra ``u = M r`` carry this stepper does not implement.
    ``block`` likewise exists for signature parity only: there is no
    shared-Krylov pipelined variant.
    """
    _no_pipelined_precond(M)
    if block:
        raise NotImplementedError(
            "pipelined_cg has no block (shared Krylov space) mode; use "
            "cg(..., block=True) or minres(..., block=True)")
    b2, _ = as2d(b)
    x = jnp.zeros_like(b2) if x0 is None else as2d(x0)[0]
    # zero-rhs columns: x = 0 is the solution — without this, a nonzero
    # x0 leaves a residual no (floored) relative tolerance ever meets
    bzero = _colsum(b2) <= 0
    x = jnp.where(bzero[None, :], jnp.zeros((), b2.dtype), x)
    r = b2 - op.mv(x)
    w = op.mv(r)
    bnorm2 = jnp.maximum(_colsum(b2), jnp.finfo(b2.dtype).tiny)
    tol2 = _tol2(tol, bnorm2)
    zeros = jnp.zeros_like(b2)
    zcol = jnp.zeros(b2.shape[1], r.dtype)
    return PCGState(x=x, r=r, w=w, z=zeros, s=zeros, p=zeros,
                    gamma_prev=zcol, alpha_prev=zcol, tol2=tol2,
                    fresh=jnp.ones(b2.shape[1], bool),
                    it=jnp.asarray(0), maxiter=jnp.asarray(int(maxiter)),
                    done=_colsum(r) <= tol2)


def _pcg_body(op, st: PCGState) -> PCGState:
    gamma = jnp.sum(st.r * st.r, axis=0)
    delta = jnp.sum(st.w * st.r, axis=0)
    q = op.mv(st.w)                      # overlaps the reduction bundle
    # per-column first-step flag (not ``it == 0``): a column refilled into
    # a running block by the SolverService starts its own recurrence
    first = st.fresh
    beta = jnp.where(
        first, 0.0,
        gamma / jnp.where(st.gamma_prev == 0, 1.0, st.gamma_prev))
    denom = jnp.where(
        first, delta,
        delta - beta * gamma
        / jnp.where(st.alpha_prev == 0, 1.0, st.alpha_prev))
    alpha = gamma / jnp.where(denom == 0, 1.0, denom)
    z = q + beta[None] * st.z
    s = st.w + beta[None] * st.s
    p = st.r + beta[None] * st.p
    a = jnp.where(st.done, 0.0, alpha)
    x = st.x + a[None] * p
    r = st.r - a[None] * s
    w = st.w - a[None] * z
    done = st.done | (_colsum(r) <= st.tol2)
    return PCGState(x=x, r=r, w=w, z=z, s=s, p=p,
                    gamma_prev=gamma, alpha_prev=alpha, tol2=st.tol2,
                    fresh=jnp.zeros_like(st.fresh),
                    it=st.it + 1, maxiter=st.maxiter, done=done)


def pipelined_cg_step(op, state: PCGState, k: int, M=None) -> PCGState:
    """Advance up to ``k`` iterations.  ``M`` must be ``None`` (raises
    :class:`NotImplementedError` otherwise — see
    :func:`pipelined_cg_init`)."""
    _no_pipelined_precond(M)
    return run_chunk(op, "pipelined_cg", k, state, _pcg_body)


def pipelined_cg_finalize(state: PCGState) -> CGResult:
    return CGResult(state.x, state.it, jnp.sqrt(_colsum(state.r)),
                    state.done)


def pipelined_cg(op, b: jax.Array, x0: Optional[jax.Array] = None, *,
                 tol: float = 1e-8, maxiter: int = 500, M=None) -> CGResult:
    """Pipelined CG (Ghysels & Vanroose 2013, Alg. 3), **unpreconditioned**.

    Passing a preconditioner raises :class:`NotImplementedError` (it used
    to be silently impossible to request one); use :func:`cg` with ``M=``
    for preconditioned solves.
    """
    _no_pipelined_precond(M)
    was1d = b.ndim == 1
    state = pipelined_cg_init(op, b, x0, tol=tol, maxiter=maxiter)
    state = pipelined_cg_step(op, state, maxiter)
    return _maybe_1d(pipelined_cg_finalize(state), was1d)
