"""Pallas TPU kernel: batched block-diagonal matmul (preconditioner apply).

The block-Jacobi preconditioner (``solvers/precond.py``) factorizes the
aligned diagonal blocks of a SELL-C-sigma matrix host-side, once, into an
explicit ``(nblocks, bs, bs)`` stack of inverse blocks.  Every PCG/PMINRES
iteration then applies ``z = diag(B_0^{-1}, ..., B_{k-1}^{-1}) r`` — a
batched small-matmul sweep with perfect locality: block ``k`` touches only
rows ``[k*bs, (k+1)*bs)`` of ``r``.

Kernel layout: one grid step owns ``row_tile`` rows (= ``row_tile/bs``
blocks).  The block stack and the vector tile stream through VMEM in
matched slabs and the batched contraction runs as one ``dot_general`` per
tile, so the apply costs a single fused sweep over ``r`` — the same
memory-bound profile as the AXPBY-class kernels (paper C2), keeping the
preconditioner on the accelerator next to the SpMV instead of bouncing to
the host.

Requires ``row_tile % bs == 0`` and inputs padded to a ``row_tile``
multiple (the :func:`repro.kernels.ops.block_jacobi_apply` wrapper pads).
Validated in interpret mode against ``block_diag_matmul_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import execution
from repro.core.spmv import storage_acc_dtype as _acc_dtype

__all__ = ["block_diag_matmul_pallas"]


def _kernel(blocks_ref, x_ref, o_ref, *, nbt: int, bs: int, b: int,
            out_dtype):
    acc_dt = _acc_dtype(out_dtype)
    bl = blocks_ref[...].astype(acc_dt)                  # (nbt, bs, bs)
    xb = x_ref[...].astype(acc_dt).reshape(nbt, bs, b)   # (nbt, bs, b)
    # batched small matmul: y[k] = B_k @ x[k] (HIGHEST: a TPU otherwise
    # runs an f32 matmul as one bfloat16 pass)
    y = jax.lax.dot_general(
        bl, xb,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=acc_dt, precision=jax.lax.Precision.HIGHEST)
    o_ref[...] = y.reshape(nbt * bs, b).astype(out_dtype)


def block_diag_matmul_pallas(
    blocks: jax.Array,            # (nblocks, bs, bs)
    x: jax.Array,                 # (nblocks * bs, b)
    *,
    row_tile: int,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``y[k*bs:(k+1)*bs] = blocks[k] @ x[k*bs:(k+1)*bs]`` for every block.

    ``row_tile`` must be a multiple of ``bs`` and divide the (padded) row
    count; the :func:`repro.kernels.ops.block_jacobi_apply` wrapper
    handles the padding.  ``interpret=None`` defers to
    :mod:`repro.core.execution`.
    """
    interpret = execution.resolve_interpret(interpret)
    nb, bs, bs2 = blocks.shape
    if bs != bs2:
        raise ValueError(f"blocks must be square, got ({bs}, {bs2})")
    n, b = x.shape
    if n != nb * bs:
        raise ValueError(f"x rows ({n}) != nblocks*bs ({nb}*{bs})")
    if row_tile % bs != 0 or row_tile <= 0:
        raise ValueError(f"row_tile ({row_tile}) must be a positive "
                         f"multiple of bs ({bs})")
    if n % row_tile != 0:
        raise ValueError(f"rows ({n}) must be a multiple of row_tile "
                         f"({row_tile}); pad first")
    nbt = row_tile // bs
    out_dtype = jnp.result_type(blocks.dtype, x.dtype)

    kern = functools.partial(_kernel, nbt=nbt, bs=bs, b=b,
                             out_dtype=out_dtype)
    return pl.pallas_call(
        kern,
        grid=(n // row_tile,),
        in_specs=[
            pl.BlockSpec((nbt, bs, bs), lambda t: (t, 0, 0)),
            pl.BlockSpec((row_tile, b), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, b), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), out_dtype),
        interpret=interpret,
    )(blocks, x)
