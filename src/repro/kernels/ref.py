"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernel tests ``assert_allclose`` against
(shape/dtype sweeps in ``tests/test_kernels.py``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import blockvec
from repro.core.sellcs import SellCS
from repro.core.spmv import SpmvOpts, spmv_ref, storage_acc_dtype

__all__ = ["sellcs_spmv_ref", "tsmttsm_ref", "tsmm_ref",
           "fused_axpby_dots_ref", "mamba_scan_ref", "block_diag_matmul_ref"]


def block_diag_matmul_ref(blocks: jax.Array, x: jax.Array) -> jax.Array:
    """Oracle for the batched block-diagonal matmul kernel.

    ``blocks`` is ``(nblocks, bs, bs)``, ``x`` is ``(nblocks*bs, b)``;
    returns ``y`` with ``y[k*bs:(k+1)*bs] = blocks[k] @ x[k*bs:(k+1)*bs]``.
    """
    nb, bs, _ = blocks.shape
    xb = x.reshape(nb, bs, x.shape[1])
    y = jnp.einsum("kij,kjb->kib", blocks, xb,
                   precision=jax.lax.Precision.HIGHEST)
    return y.reshape(nb * bs, x.shape[1])


def mamba_scan_ref(dt, xc, Bc, Cc, A):
    """Oracle for the state-resident Mamba scan kernel: plain lax.scan."""
    B, S, di = dt.shape

    def step(h, t_in):
        dt_t, xc_t, Bc_t, Cc_t = t_in
        dA = jnp.exp(dt_t[..., None] * A[None])
        dBx = (dt_t * xc_t)[..., None] * Bc_t[:, None, :]
        h = dA * h + dBx
        y = jnp.einsum("bdn,bn->bd", h, Cc_t)
        return h, y

    h0 = jnp.zeros((B, di, A.shape[1]), jnp.float32)
    _, ys = jax.lax.scan(
        step, h0, tuple(jnp.moveaxis(a, 1, 0) for a in (dt, xc, Bc, Cc)))
    return jnp.moveaxis(ys, 0, 1)


def sellcs_spmv_ref(A: SellCS, x, y=None, z=None, opts: SpmvOpts = SpmvOpts()):
    """Delegates to the core segment-sum oracle."""
    return spmv_ref(A, x, y, z, opts)


def tsmttsm_ref(V, W, X=None, alpha=1.0, beta=0.0, *, conj=True):
    return blockvec.tsmttsm(V, W, X, alpha=alpha, beta=beta, conj=conj)


def tsmm_ref(V, X, W=None, alpha=1.0, beta=0.0):
    return blockvec.tsmm(V, X, W, alpha=alpha, beta=beta)


def fused_axpby_dots_ref(
    x: jax.Array, y: jax.Array, a=1.0, b=1.0,
    *, dot_yy=False, dot_xy=False, dot_xx=False,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    acc = storage_acc_dtype(x.dtype)   # shared storage-vs-compute contract
    xf = x.astype(acc)
    yf = y.astype(acc)
    ynew = jnp.asarray(a, acc) * xf + jnp.asarray(b, acc) * yf
    dots = None
    if dot_yy or dot_xy or dot_xx:
        bw = x.shape[1]
        zero = jnp.zeros((bw,), acc)
        dots = jnp.stack([
            jnp.sum(ynew * ynew, axis=0) if dot_yy else zero,
            jnp.sum(xf * ynew, axis=0) if dot_xy else zero,
            jnp.sum(xf * xf, axis=0) if dot_xx else zero,
        ])
    return ynew.astype(x.dtype), dots
