"""Pallas TPU kernel: tall-skinny^H x tall-skinny GEMM (paper C2, Fig. 7).

``X = alpha * V^H W + beta * X`` with V ``(n, m)``, W ``(n, k)``, m,k << n.

The paper's observation: vendor GEMMs are built for square blocking and
collapse on tall & skinny shapes, where the kernel is *memory bound* (2n(m+k)
words moved for 2nmk flops).  The TPU-native design streams ``(Tn, m)`` /
``(Tn, k)`` row slabs through VMEM, runs an ``(m, Tn) @ (Tn, k)`` MXU matmul
per slab, and accumulates the tiny ``(m, k)`` result in a float32 VMEM
scratch across the sequential grid — one HBM sweep, no re-reads.

A Kahan-compensated variant keeps a second ``(m, k)`` compensation buffer in
VMEM (paper section 5.2: compensated tsmttsm at negligible flop overhead).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import execution
from repro.core.spmv import storage_acc_dtype as _acc_dtype

__all__ = ["tsmttsm_pallas"]

# every dot_general here asks for full f32 precision: a TPU otherwise
# runs an f32 matmul as one bfloat16 pass
_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(v_ref, w_ref, coef_ref, xin_ref, out_ref, acc_ref, comp_ref,
            *, kahan: bool, conj: bool, has_xin: bool, out_dtype):
    i = pl.program_id(0)
    nsteps = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if kahan:
            comp_ref[...] = jnp.zeros_like(comp_ref)

    acc_dt = acc_ref.dtype

    def slabs(rows):
        v = v_ref[rows, :].astype(acc_dt)
        if conj:
            v = jnp.conj(v)
        return v, w_ref[rows, :].astype(acc_dt)

    if kahan:
        # Compensation can only absorb error *between* summands, so a
        # single (row_tile)-deep dot would leave its internal rounding
        # uncompensated.  Walk the slab in 8-row micro-slabs (8 = VPU
        # sublane height; smaller divisor for odd tiles) and Kahan-
        # accumulate one 2-D dot per micro-slab.  The micro-slabs are
        # read straight from the refs at aligned offsets: Mosaic has no
        # lowering for dynamic_slice on loaded values, nor for a batched
        # rank-3 dot_general.  The uncompensated window shrinks from
        # row_tile to g rows.
        g = next(d for d in (8, 4, 2, 1) if v_ref.shape[0] % d == 0)
        G = v_ref.shape[0] // g

        def body(j, carry):
            acc, comp = carry
            vs, ws = slabs(pl.ds(pl.multiple_of(j * g, g), g))
            part = jax.lax.dot_general(
                vs, ws, (((0,), (0,)), ((), ())),
                preferred_element_type=acc_dt, precision=_HIGHEST)
            y = part - comp
            t = acc + y
            return t, (t - acc) - y

        acc, comp = jax.lax.fori_loop(
            0, G, body, (acc_ref[...], comp_ref[...]))
        acc_ref[...] = acc
        comp_ref[...] = comp
    else:
        v, w = slabs(slice(None))
        term = jax.lax.dot_general(
            v, w, (((0,), (0,)), ((), ())), preferred_element_type=acc_dt,
            precision=_HIGHEST)
        acc_ref[...] = acc_ref[...] + term

    @pl.when(i == nsteps - 1)
    def _fin():
        alpha = coef_ref[0, 0]
        beta = coef_ref[0, 1]
        res = alpha * acc_ref[...]
        if has_xin:
            res = res + beta * xin_ref[...].astype(acc_dt)
        out_ref[...] = res.astype(out_dtype)


def tsmttsm_pallas(
    V: jax.Array,
    W: jax.Array,
    X: Optional[jax.Array] = None,
    alpha=1.0,
    beta=0.0,
    *,
    row_tile: int = 512,
    kahan: bool = False,
    conj: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """X = alpha * V^H W + beta * X.  Requires n % row_tile == 0 (ops.py pads).

    ``interpret=None`` defers to :mod:`repro.core.execution`.
    """
    from repro.core.blockvec import check_beta_needs_out
    check_beta_needs_out(beta, X, "tsmttsm_pallas")  # beta*X with X=None
    interpret = execution.resolve_interpret(interpret)  # would vanish
    n, m = V.shape
    n2, k = W.shape
    if n != n2:
        raise ValueError(
            f"tsmttsm: row counts disagree: V{V.shape} W{W.shape}")
    if n % row_tile != 0:
        raise ValueError(f"tsmttsm: n={n} not a multiple of "
                         f"row_tile={row_tile} (ops.py pads)")
    out_dtype = jnp.result_type(V.dtype, W.dtype)
    acc_dt = _acc_dtype(out_dtype)
    do_conj = conj and jnp.iscomplexobj(V)

    coefs = jnp.stack([jnp.asarray(alpha, acc_dt),
                       jnp.asarray(beta, acc_dt)]).reshape(1, 2)
    has_xin = X is not None
    xin = X if has_xin else jnp.zeros((m, k), out_dtype)

    grid = (n // row_tile,)
    kern = functools.partial(
        _kernel, kahan=kahan, conj=do_conj, has_xin=has_xin,
        out_dtype=out_dtype)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, m), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, k), lambda i: (i, 0)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
            pl.BlockSpec((m, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, k), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((m, k), acc_dt),
            pltpu.VMEM((m, k), acc_dt),
        ],
        interpret=interpret,
    )(V, W, coefs, xin)
    return out
