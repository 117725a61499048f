"""Pallas TPU kernel: fused SELL-C-sigma SpM(M)V (paper C1 + C3).

TPU adaptation of GHOST's SIMD SELL kernel:

* chunk height C = 128 (VPU lane count) by default; one grid step owns one
  chunk and produces a ``(C, b)`` output tile in VMEM.
* ``vals``/``cols`` live in ``pl.ANY`` (compiler-placed, HBM for large
  matrices) and are streamed in ``(w_tile, C)`` slabs — the chunk-column-
  major layout makes every slab load contiguous, exactly the property the
  paper engineered for wide SIMD.
* per-chunk ragged widths arrive via scalar prefetch (``chunk_off``,
  ``chunk_len``), the TPU-idiomatic replacement for GHOST's chunk pointer
  arithmetic; the inner ``fori_loop`` has a data-dependent trip count so
  short chunks do no wasted slab loads (this is what sigma-sorting buys).
* the gather ``x[cols]`` is the irreducible scatter/gather of SpMV.  On GPU
  the paper leans on the texture cache; on TPU we keep ``x`` compiler-placed
  and issue vector gathers.  In the *distributed* path the remote part
  gathers from a small compressed halo buffer that fits VMEM (see
  ``core/distributed.py``), which is the TPU-native analogue of GHOST's
  compressed remote columns (paper Fig. 3).

Fusion flags (alpha/beta/gamma shift, chained axpby, three dot products) are
*static* Python switches: each flag combination traces a specialized kernel,
mirroring GHOST's compile-time code generation (paper C6).  Scalar
coefficients arrive in a packed ``(1, 4)`` operand so they may be traced
values inside jitted solvers.

The same C6 specialization applies over *data types*: ``vals`` may be a
narrower **storage** dtype (bf16/f16) than the ``compute_dtype`` the caller
accumulates in — each ``(w_tile, C)`` value slab streams from HBM at the
storage width and is upcast in-register before the ``einsum``, halving the
dominant memory traffic of this bandwidth-bound kernel
(``docs/mixed_precision.md``).

Validated in ``interpret=True`` mode against ``core.spmv.spmv_ref``.  It
has no compiled form yet: Mosaic refuses the ``x_ref[cslab]`` gather from
the ``pl.ANY``-placed ``x`` (see :data:`NO_LOWERING`), so a compiled call
raises and the SpMV runs as ``impl="ref"`` on the chip.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import execution
from repro.core.spmv import (compensated_sum0, dot_acc_dtype,
                             storage_acc_dtype as _acc_dtype)

__all__ = ["sellcs_spmv_pallas", "NO_LOWERING"]

#: why a compiled call is refused: what Mosaic (JAX 0.9.0) reports when it
#: lowers this kernel for a TPU v5e
NO_LOWERING = (
    "sellcs_spmv_pallas has no Mosaic lowering: the x[cols] gather reads "
    "a pl.ANY ref, and Mosaic refuses it with 'Loads are only allowed on "
    "VMEM and SMEM references. ANY memory space can only be accessed "
    "using async_copy.'  Run the SpMV with impl='ref'.")


def _kernel(
    # scalar prefetch
    off_ref, len_ref,
    # inputs
    vals_ref, cols_ref, x_ref, coef_ref, *rest,
    C: int, b: int, w_tile: int,
    has_yin: bool, has_gamma: bool, chain: bool,
    dot_yy: bool, dot_xy: bool, dot_xx: bool,
    out_dtype,
):
    ri = 0
    yin_ref = rest[ri] if has_yin else None
    ri += has_yin
    zin_ref = rest[ri] if chain else None
    ri += chain
    gamma_ref = rest[ri] if has_gamma else None
    ri += has_gamma
    outs = rest[ri:]
    y_ref = outs[0]
    oi = 1
    z_ref = outs[oi] if chain else None
    oi += chain
    dots_ref = outs[oi] if (dot_yy or dot_xy or dot_xx) else None

    c = pl.program_id(0)
    off = off_ref[c]
    ntiles = len_ref[c] // w_tile

    acc_dt = _acc_dtype(out_dtype)
    acc0 = jnp.zeros((C, b), acc_dt)

    def body(j, acc):
        base = (off + j * w_tile) * C
        cslab = cols_ref[pl.ds(base, w_tile * C)]
        vslab = vals_ref[pl.ds(base, w_tile * C)].astype(acc_dt)
        xg = x_ref[cslab]                              # (w_tile*C, b) gather
        xg = xg.reshape(w_tile, C, b).astype(acc_dt)
        vslab = vslab.reshape(w_tile, C)
        return acc + jnp.einsum("wc,wcb->cb", vslab, xg)

    acc = lax.fori_loop(0, ntiles, body, acc0)

    alpha = coef_ref[0, 0]
    beta = coef_ref[0, 1]
    delta = coef_ref[0, 2]
    eta = coef_ref[0, 3]

    need_xrow = has_gamma or dot_xy or dot_xx
    if need_xrow:
        xrow = x_ref[pl.ds(c * C, C), :].astype(acc_dt)
    if has_gamma:
        g = gamma_ref[...].astype(acc_dt)              # (1, b) or (1, 1)
        acc = acc - g * xrow

    y = alpha * acc
    if has_yin:
        y = y + beta * yin_ref[...].astype(acc_dt)
    y_ref[...] = y.astype(out_dtype)

    if chain:
        z = delta * zin_ref[...].astype(acc_dt) + eta * y
        z_ref[...] = z.astype(out_dtype)

    if dots_ref is not None:
        dt = dots_ref.dtype
        zero = jnp.zeros((b,), dt)
        d_yy = jnp.sum(y * y, axis=0).astype(dt) if dot_yy else zero
        d_xy = jnp.sum(xrow * y, axis=0).astype(dt) if dot_xy else zero
        d_xx = jnp.sum(xrow * xrow, axis=0).astype(dt) if dot_xx else zero
        dots_ref[...] = jnp.stack([d_yy, d_xy, d_xx])[None]


def sellcs_spmv_pallas(
    vals: jax.Array,
    cols: jax.Array,
    chunk_off: jax.Array,
    chunk_len: jax.Array,
    x: jax.Array,                      # (n_pad, b), permuted space
    y_in: Optional[jax.Array] = None,  # (n_pad, b)
    z_in: Optional[jax.Array] = None,
    gamma: Optional[jax.Array] = None,  # (b,) or scalar shift
    *,
    C: int,
    w_tile: int,
    alpha=1.0,
    beta=0.0,
    delta=None,
    eta=None,
    dot_yy: bool = False,
    dot_xy: bool = False,
    dot_xx: bool = False,
    compute_dtype=None,
    interpret: Optional[bool] = None,
):
    """Run the fused SELL-C-sigma SpMMV kernel.

    Requires ``chunk_len % w_tile == 0`` (build the matrix with
    ``w_align=w_tile``) — validated host-side whenever ``chunk_len`` is
    concrete, because the kernel's ``len // w_tile`` trip count would
    otherwise silently drop the tail nonzeros of every ragged chunk.
    Returns ``(y, z, dots)`` where ``dots`` is ``(3, b)`` (yy, xy, xx)
    summed over chunks, or ``None``.  ``interpret=None`` defers to
    :mod:`repro.core.execution`.

    ``compute_dtype`` pins the output/accumulation dtype explicitly (the
    storage-vs-compute contract: pass ``SellCS.dtype`` when ``vals`` is
    stored narrower).  ``None`` falls back to type promotion over
    ``vals``/``x`` — identical for single-dtype matrices.  Either way a
    sub-32-bit value slab is upcast **in-register** (``(w_tile, C)`` tile
    cast inside the fori_loop body) so HBM traffic stays at the storage
    width while the accumulator is at least f32.
    """
    interpret = execution.resolve_interpret(interpret)
    if not interpret:
        raise NotImplementedError(NO_LOWERING)
    if w_tile <= 0:
        raise ValueError(f"w_tile must be positive, got {w_tile}")
    if not isinstance(chunk_len, jax.core.Tracer):
        rem = np.asarray(chunk_len) % w_tile
        if rem.any():
            bad = np.nonzero(rem)[0]
            raise ValueError(
                f"chunk_len % w_tile != 0 for chunks {bad[:8].tolist()}"
                f"{'...' if len(bad) > 8 else ''} (w_tile={w_tile}): the "
                f"kernel would silently drop tail nonzeros — rebuild the "
                f"matrix with w_align={w_tile} or pass a compatible w_tile")
    b = x.shape[1]
    nchunks = int(chunk_off.shape[0])
    n_pad = nchunks * C                      # output rows (may differ from
    square = x.shape[0] == n_pad             # x rows for rectangular parts)
    if compute_dtype is None:
        out_dtype = jnp.result_type(vals.dtype, x.dtype)
    else:
        out_dtype = jnp.result_type(jnp.dtype(compute_dtype), x.dtype)
    acc_dt = _acc_dtype(out_dtype)
    has_yin = y_in is not None
    chain = delta is not None or eta is not None
    has_gamma = gamma is not None
    any_dot = dot_yy or dot_xy or dot_xx
    if (has_gamma or dot_xy or dot_xx) and not square:
        raise ValueError("gamma shift / x-dots need a square (diag-aligned) part")

    coefs = jnp.stack([
        jnp.asarray(alpha, acc_dt),
        jnp.asarray(beta, acc_dt),
        jnp.asarray(0.0 if delta is None else delta, acc_dt),
        jnp.asarray(0.0 if eta is None else eta, acc_dt),
    ]).reshape(1, 4)

    inputs = [vals, cols, x, coefs]
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec((1, 4), lambda c, off, ln: (0, 0)),
    ]
    tile_spec = pl.BlockSpec((C, b), lambda c, off, ln: (c, 0))
    if has_yin:
        inputs.append(y_in)
        in_specs.append(tile_spec)
    if chain:
        if z_in is None:
            raise ValueError("sellcs_spmv: chained axpby requires z_in")
        inputs.append(z_in)
        in_specs.append(tile_spec)
    if has_gamma:
        g = jnp.atleast_1d(jnp.asarray(gamma)).reshape(1, -1)
        if g.shape[1] not in (1, b):
            raise ValueError(f"gamma must be scalar or ({b},)")
        gw = g.shape[1]
        inputs.append(g)
        in_specs.append(pl.BlockSpec((1, gw), lambda c, off, ln: (0, 0)))

    out_shapes = [jax.ShapeDtypeStruct((n_pad, b), out_dtype)]
    out_specs = [tile_spec]
    if chain:
        out_shapes.append(jax.ShapeDtypeStruct((n_pad, b), out_dtype))
        out_specs.append(tile_spec)
    if any_dot:
        out_shapes.append(jax.ShapeDtypeStruct((nchunks, 3, b), acc_dt))
        out_specs.append(pl.BlockSpec((1, 3, b), lambda c, off, ln: (c, 0, 0)))

    kern = functools.partial(
        _kernel,
        C=C, b=b, w_tile=w_tile,
        has_yin=has_yin, has_gamma=has_gamma, chain=chain,
        dot_yy=dot_yy, dot_xy=dot_xy, dot_xx=dot_xx,
        out_dtype=out_dtype,
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nchunks,),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    outs = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
    )(chunk_off, chunk_len, *inputs)

    y = outs[0]
    oi = 1
    z = None
    if chain:
        z = outs[oi]
        oi += 1
    dots = None
    if any_dot:
        # per-chunk partials reduce in f64 when available, Kahan-
        # compensated otherwise (paper's augmented-SpMV accuracy claim;
        # cast at this boundary only)
        part = outs[oi].astype(dot_acc_dtype(acc_dt))        # (nchunks, 3, b)
        if jnp.finfo(part.dtype).bits >= 64:
            dots = part.sum(axis=0)
        else:
            dots = compensated_sum0(part, block=8)
    return y, z, dots
