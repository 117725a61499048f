"""Lanczos tridiagonalization and extremal eigenvalue estimation.

A GHOST sample application (the paper ships "a Lanczos eigensolver" with the
library) and the engine behind the spectral-interval estimation that KPM and
Chebyshev filter diagonalization require.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# full f32 precision in every contraction: a TPU otherwise runs an f32
# matmul as one bfloat16 pass
_HIGHEST = jax.lax.Precision.HIGHEST


class LanczosResult(NamedTuple):
    alphas: jax.Array      # (k,)   entries past nvalid are zero padding
    betas: jax.Array       # (k-1,) entries past nvalid-1 are zero padding
    V: Optional[jax.Array]  # (n, k) basis if kept (zero columns past nvalid)
    nvalid: Optional[jax.Array] = None  # () number of valid Lanczos steps
    #                                     (< k after a happy breakdown)


def randn(key, shape, dtype) -> jax.Array:
    """Gaussian start block in the operator's dtype (complex-aware).

    Internally generated Lanczos/ChebFD start vectors must match
    ``op.dtype`` — a hardcoded float32 start silently downcasts an f64
    operator's whole Krylov recurrence.
    """
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.complexfloating):
        rdt = jnp.finfo(dtype).dtype            # matching real dtype
        kre, kim = jax.random.split(key)
        return (jax.random.normal(kre, shape, rdt)
                + 1j * jax.random.normal(kim, shape, rdt)).astype(dtype)
    return jax.random.normal(key, shape, dtype)


def lanczos(op, v0: jax.Array, k: int, *, reorth: bool = False,
            keep_basis: bool = False, seed: int = 0) -> LanczosResult:
    """k-step Lanczos on symmetric/Hermitian op.  v0 (n,) start (or None)."""
    n = op.n
    if v0 is None:
        v0 = randn(jax.random.PRNGKey(seed), (n,), op.dtype)
    v = v0 / jnp.linalg.norm(v0)

    rdt = jnp.finfo(v.dtype).dtype              # real dtype of the recurrence
    alphas = jnp.zeros(k, rdt)
    betas = jnp.zeros(max(k - 1, 1), rdt)
    V = jnp.zeros((n, k), v.dtype) if (keep_basis or reorth) else None

    v_prev = jnp.zeros_like(v)
    beta = jnp.asarray(0.0, rdt)
    # breakdown tracking: once beta hits 0 the Krylov space is exhausted
    # (happy breakdown) — recurring on w = 0 would keep appending garbage
    # zero alphas/betas that poison the tridiagonal's spectrum.  The loop
    # stays unrolled/traceable, so "stop" is a mask: frozen steps write
    # nothing and nvalid reports the usable prefix.
    alive = jnp.asarray(True)
    nvalid = jnp.asarray(0, jnp.int32)
    for j in range(k):                      # unrolled: k is small & static
        if V is not None:
            V = V.at[:, j].set(jnp.where(alive, v, jnp.zeros_like(v)))
        w = op.mv(v[:, None])[:, 0]
        alpha = jnp.vdot(v, w, precision=_HIGHEST)
        w = w - alpha * v - beta * v_prev
        if reorth and V is not None:
            # conjugate transpose: for complex Hermitian operators the
            # projector is V V^H, not V V^T
            w = w - jnp.matmul(V, jnp.matmul(V.conj().T, w,
                                             precision=_HIGHEST),
                               precision=_HIGHEST)
        alphas = alphas.at[j].set(jnp.where(alive, alpha.real, 0.0))
        nvalid = nvalid + alive.astype(jnp.int32)
        beta_new = jnp.linalg.norm(w).astype(rdt)
        step_alive = alive & (beta_new > 0)
        if j < k - 1:
            betas = betas.at[j].set(jnp.where(step_alive, beta_new, 0.0))
        v_prev = v
        v = jnp.where(step_alive,
                      w / jnp.where(beta_new == 0, 1.0, beta_new), v)
        beta = jnp.where(step_alive, beta_new, jnp.zeros((), rdt))
        alive = step_alive
    return LanczosResult(alphas, betas[: max(k - 1, 0)], V, nvalid)


def tridiag_eigh(alphas, betas) -> Tuple[np.ndarray, np.ndarray]:
    """Eigendecomposition of the Lanczos tridiagonal (host-side)."""
    try:
        from scipy.linalg import eigh_tridiagonal
        return eigh_tridiagonal(np.asarray(alphas), np.asarray(betas))
    except ImportError:                      # pragma: no cover
        a = np.asarray(alphas)
        b = np.asarray(betas)
        T = np.diag(a) + np.diag(b, 1) + np.diag(b, -1)
        return np.linalg.eigh(T)


def lanczos_extrema(op, *, k: int = 30, seed: int = 0,
                    safety: float = 1.05) -> Tuple[float, float]:
    """Estimate (lambda_min, lambda_max) with a short Lanczos run, widened
    by ``safety`` — the spectral scaling KPM/ChebFD need.  Only the
    valid prefix of the recurrence enters the tridiagonal: after a happy
    breakdown the padded zero alphas would drag a spurious 0 into the
    spectrum estimate."""
    res = lanczos(op, None, k, seed=seed)
    nv = k if res.nvalid is None else max(int(res.nvalid), 1)
    ev, _ = tridiag_eigh(np.asarray(res.alphas)[:nv],
                         np.asarray(res.betas)[:max(nv - 1, 0)])
    lo, hi = float(ev[0]), float(ev[-1])
    mid, rad = (hi + lo) / 2, (hi - lo) / 2
    rad = max(rad * safety, 1e-12)
    return mid - rad, mid + rad
