"""Pallas TPU kernel: tall-skinny times small GEMM (paper C2, Fig. 7).

``W = alpha * V X + beta * W`` with V ``(n, m)``, X ``(m, k)``, m,k << n.
Embarrassingly row-parallel: the small X stays VMEM-resident across the
whole sweep, each grid step streams one ``(Tn, m)`` slab of V in and one
``(Tn, k)`` slab of W out — one read + one write per element, the memory-
bound optimum the paper's model prescribes.

The in-place variant (``tsmm_inplace``) is realised functionally with input/
output aliasing (donation) at the ops layer.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import execution
from repro.core.spmv import storage_acc_dtype as _acc_dtype

__all__ = ["tsmm_pallas"]


def _kernel(v_ref, x_ref, coef_ref, win_ref, out_ref, *,
            has_win: bool, out_dtype):
    acc_dt = _acc_dtype(out_dtype)
    v = v_ref[...].astype(acc_dt)
    x = x_ref[...].astype(acc_dt)
    # HIGHEST: a TPU otherwise runs an f32 matmul as one bfloat16 pass
    prod = jax.lax.dot_general(
        v, x, (((1,), (0,)), ((), ())), preferred_element_type=acc_dt,
        precision=jax.lax.Precision.HIGHEST)
    alpha = coef_ref[0, 0]
    res = alpha * prod
    if has_win:
        beta = coef_ref[0, 1]
        res = res + beta * win_ref[...].astype(acc_dt)
    out_ref[...] = res.astype(out_dtype)


def tsmm_pallas(
    V: jax.Array,
    X: jax.Array,
    W: Optional[jax.Array] = None,
    alpha=1.0,
    beta=0.0,
    *,
    row_tile: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """W = alpha * V @ X + beta * W.  Requires n % row_tile == 0 (ops.py pads).

    ``interpret=None`` defers to :mod:`repro.core.execution`.
    """
    from repro.core.blockvec import check_beta_needs_out
    check_beta_needs_out(beta, W, "tsmm_pallas")   # beta*W with W=None would
    interpret = execution.resolve_interpret(interpret)   # silently vanish
    n, m = V.shape
    m2, k = X.shape
    if m != m2:
        raise ValueError(f"tsmm: inner dims disagree: V{V.shape} X{X.shape}")
    if n % row_tile != 0:
        raise ValueError(f"tsmm: n={n} not a multiple of "
                         f"row_tile={row_tile} (ops.py pads)")
    out_dtype = jnp.result_type(V.dtype, X.dtype)
    acc_dt = _acc_dtype(out_dtype)
    has_win = W is not None
    win = W if has_win else jnp.zeros((1, k), out_dtype)

    coefs = jnp.stack([jnp.asarray(alpha, acc_dt),
                       jnp.asarray(beta, acc_dt)]).reshape(1, 2)
    grid = (n // row_tile,)
    kern = functools.partial(_kernel, has_win=has_win, out_dtype=out_dtype)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, m), lambda i: (i, 0)),
            pl.BlockSpec((m, k), lambda i: (0, 0)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
            (pl.BlockSpec((row_tile, k), lambda i: (i, 0)) if has_win
             else pl.BlockSpec((1, k), lambda i: (0, 0))),
        ],
        out_specs=pl.BlockSpec((row_tile, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), out_dtype),
        interpret=interpret,
    )(V, X, coefs, win)
    return out
