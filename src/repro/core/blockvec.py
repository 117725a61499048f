"""Block vectors — tall & skinny dense matrices (paper C2).

A block vector is ``(n, b)`` with small ``b``; "row-major" interleaved
storage is the JAX-native layout (last axis minor).  The paper's
column-major variant is represented as ``(b, n)`` and exists to reproduce
the layout study (Fig. 8); all compute prefers row-major.

Implements GHOST's tall-skinny kernels and blocked BLAS-1:

    tsmttsm : X = alpha * V^H W + beta * X      (inner product of blocks)
    tsmm    : W = alpha * V X + beta * W        (block times small matrix)
    tsmm_inplace
    axpy / axpby / scal / dot  (+ v-variants with per-column scalars)
    Kahan-compensated tsmttsm and dot (paper section 5.2)

Scattered views (column subsets) and compact clones mirror Fig. 2.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

# full f32 precision in every contraction: a TPU otherwise runs an f32
# matmul as one bfloat16 pass
_HIGHEST = jax.lax.Precision.HIGHEST

__all__ = [
    "tsmttsm", "tsmm", "tsmm_inplace", "axpy", "axpby", "scal", "dot",
    "vaxpy", "vaxpby", "vscal", "tsmttsm_kahan", "dot_kahan",
    "view_cols", "compact_clone", "to_col_major", "to_row_major",
]


# ----------------------------------------------------------------- views
def view_cols(v: jax.Array, cols: Sequence[int]) -> jax.Array:
    """A (possibly scattered) view of selected block-vector columns."""
    return v[:, jnp.asarray(list(cols))]


def compact_clone(v: jax.Array) -> jax.Array:
    """Force a compact copy (paper: clone a scattered view before compute)."""
    return jnp.array(v)


def to_col_major(v: jax.Array) -> jax.Array:
    return v.T


def to_row_major(v: jax.Array) -> jax.Array:
    return v.T


def check_beta_needs_out(beta, out, fn: str) -> None:
    """A nonzero ``beta`` without the output operand would silently drop
    the ``beta * out`` term — raise instead of computing the wrong thing.

    A traced ``beta`` cannot be proven zero, so it is rejected too: pass
    the output block, or a concrete ``beta=0``.
    """
    if out is not None:
        return
    try:
        beta_zero = bool(beta == 0)
    except jax.errors.ConcretizationTypeError:
        beta_zero = False
    if not beta_zero:
        raise ValueError(
            f"{fn}: beta != 0 (or traced beta) without the output operand "
            f"— the beta term would be silently dropped; pass the output "
            f"block or leave beta=0")


# ------------------------------------------------------- tall-skinny GEMMs
def tsmttsm(V: jax.Array, W: jax.Array, X: Optional[jax.Array] = None,
            alpha=1.0, beta=0.0, *, conj: bool = True) -> jax.Array:
    """X = alpha * V^H W + beta * X with f32->f32 / widened accumulation.

    V: (n, m), W: (n, k) -> (m, k).  The reduction runs in the widest of
    the input dtypes (f32 inputs accumulate in f32 here; the Pallas kernel
    accumulates in f32 VMEM scratch and the Kahan variant compensates).
    """
    check_beta_needs_out(beta, X, "tsmttsm")
    Vh = jnp.conj(V) if (conj and jnp.iscomplexobj(V)) else V
    prod = jnp.einsum("nm,nk->mk", Vh, W, precision=_HIGHEST,
                      preferred_element_type=_acc_dtype(V.dtype, W.dtype))
    out = alpha * prod
    if X is not None:
        out = out + beta * X.astype(out.dtype)
    return out


def tsmm(V: jax.Array, X: jax.Array, W: Optional[jax.Array] = None,
         alpha=1.0, beta=0.0) -> jax.Array:
    """W = alpha * V X + beta * W.   V: (n, m), X: (m, k) -> (n, k)."""
    check_beta_needs_out(beta, W, "tsmm")
    prod = jnp.einsum("nm,mk->nk", V, X, precision=_HIGHEST,
                      preferred_element_type=_acc_dtype(V.dtype, X.dtype))
    out = alpha * prod
    if W is not None:
        out = out + beta * W.astype(out.dtype)
    return out.astype(jnp.result_type(V.dtype, X.dtype))


def tsmm_inplace(V: jax.Array, X: jax.Array, alpha=1.0, beta=0.0) -> jax.Array:
    """V = alpha * V X + beta * V (functional 'in-place': donate V at jit)."""
    return tsmm(V, X, V, alpha=alpha, beta=beta)


def _acc_dtype(a, b):
    r = jnp.result_type(a, b)
    if r == jnp.bfloat16 or r == jnp.float16:
        return jnp.float32
    return r


# ---------------------------------------------------------------- BLAS-1(.5)
def axpy(y, x, a=1.0):
    return y + a * x


def axpby(y, x, a=1.0, b=1.0):
    return b * y + a * x


def scal(x, a):
    return a * x


def dot(x, y) -> jax.Array:
    """Column-wise <x, y> (conjugated first argument)."""
    xc = jnp.conj(x) if jnp.iscomplexobj(x) else x
    return jnp.sum(xc * y, axis=0)


def vaxpy(y, x, a):
    """Per-column scalars a: (b,)."""
    return y + jnp.asarray(a)[None, :] * x


def vaxpby(y, x, a, b):
    return jnp.asarray(b)[None, :] * y + jnp.asarray(a)[None, :] * x


def vscal(x, a):
    return jnp.asarray(a)[None, :] * x


# -------------------------------------------------------------------- Kahan
def _kahan_reduce(terms: jax.Array) -> jax.Array:
    """Compensated (Kahan) summation over axis 0 via lax.scan."""
    def step(carry, t):
        s, c = carry
        yk = t - c
        tk = s + yk
        c = (tk - s) - yk
        return (tk, c), None

    zero = jnp.zeros(terms.shape[1:], terms.dtype)
    (s, _), _ = jax.lax.scan(step, (zero, zero), terms)
    return s


def dot_kahan(x, y, *, block: int = 256) -> jax.Array:
    """Kahan-compensated column-wise dot.

    Blocks of ``block`` rows are reduced pairwise (exact in the roofline
    sense: still one sweep over memory), and the block partials are combined
    with Kahan compensation — matching GHOST's compensated tsmttsm whose
    extra flops are negligible for wide-enough blocks.
    """
    n = x.shape[0]
    nb = max(1, -(-n // block))
    pad = nb * block - n
    xc = jnp.conj(x) if jnp.iscomplexobj(x) else x
    t = (xc * y)
    if pad:
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
    t = t.reshape(nb, block, *t.shape[1:]).sum(axis=1)
    return _kahan_reduce(t)


def tsmttsm_kahan(V: jax.Array, W: jax.Array, *, block: int = 256) -> jax.Array:
    """Kahan-compensated V^H W (paper's compensated inner product)."""
    n, m = V.shape
    k = W.shape[1]
    nb = max(1, -(-n // block))
    pad = nb * block - n
    Vh = jnp.conj(V) if jnp.iscomplexobj(V) else V
    if pad:
        Vh = jnp.pad(Vh, ((0, pad), (0, 0)))
        W = jnp.pad(W, ((0, pad), (0, 0)))
    Vb = Vh.reshape(nb, block, m)
    Wb = W.reshape(nb, block, k)
    partials = jnp.einsum("zbm,zbk->zmk", Vb, Wb, precision=_HIGHEST,
                          preferred_element_type=_acc_dtype(V.dtype, W.dtype))
    return _kahan_reduce(partials)
