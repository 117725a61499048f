"""Augmented, fused SpM(M)V (paper C1 + C3).

The single entry point mirrors GHOST's ``ghost_spmv(y, A, x, opts)``:

    y = alpha * (A - gamma*I) @ x + beta * y          (VSHIFT: gamma per column)
    z = delta * z + eta * y                            (chained AXPBY)
    dots = [<y,y>, <x,y>, <x,x>]  (per block-vector column, f64 or Kahan acc)

Every augmentation is individually switchable, exactly like the paper's
``GHOST_SPMV_*`` flags.  ``x``/``y``/``z`` may be single vectors ``(n,)`` or
block vectors ``(n, b)`` (row-major interleaved storage — paper section 5.2).

Two executors:
  * ``impl='ref'``     — pure jnp (segment-sum) oracle, runs anywhere.
  * ``impl='pallas'``  — the SELL-C-sigma Pallas TPU kernel (fused sweep).

All vectors live in the matrix' *permuted* space of length ``nrows_pad``
(see ``core.sellcs``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blockvec
from repro.core.sellcs import SellCS
from repro.core.trace import scope

__all__ = ["SpmvOpts", "as2d", "pack_coefs", "spmv", "spmv_ref",
           "dot_acc_dtype", "storage_acc_dtype", "compensated_sum0",
           "fused_dots"]


def storage_acc_dtype(dt):
    """Accumulator dtype for a given operand/output dtype.

    The storage-vs-compute contract shared by every value-stream kernel
    (``sellcs_spmv``, ``block_diag``, ``fused_update``): sub-32-bit floats
    (``bfloat16``/``float16``) are *storage* formats — loads upcast
    in-register and the accumulator is at least ``float32``; 32/64-bit
    floats accumulate natively.  See ``docs/mixed_precision.md``.
    """
    dt = jnp.dtype(dt)
    if dt in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)):
        return jnp.dtype(jnp.float32)
    return dt


@dataclasses.dataclass(frozen=True)
class SpmvOpts:
    """Fusion flags for the augmented SpMV (GHOST ``ghost_spmv_opts``)."""

    alpha: float | jax.Array = 1.0
    beta: float | jax.Array = 0.0         # y = alpha*Ax + beta*y
    gamma: Optional[jax.Array] = None     # scalar or (b,) per-column shift
    delta: Optional[jax.Array] = None     # z = delta*z + eta*y  (needs eta too)
    eta: Optional[jax.Array] = None
    dot_yy: bool = False
    dot_xy: bool = False
    dot_xx: bool = False

    @property
    def any_dot(self) -> bool:
        return self.dot_yy or self.dot_xy or self.dot_xx

    @property
    def chain_axpby(self) -> bool:
        return self.delta is not None or self.eta is not None


def pack_coefs(opts: SpmvOpts, nvecs: int, dtype) -> jax.Array:
    """Pack (alpha, beta, gamma) into a traced ``(3, nvecs)`` operand.

    Matvec builders that take coefficients as runtime arrays (so solvers
    can vary them per iteration without retracing — see
    ``repro.runtime.pipeline.make_pipeline_spmv``) use this layout; the
    static flags of ``opts`` stay trace-time switches.
    """
    c = jnp.zeros((3, nvecs), dtype)
    c = c.at[0].set(jnp.broadcast_to(jnp.asarray(opts.alpha, dtype), (nvecs,)))
    c = c.at[1].set(jnp.broadcast_to(jnp.asarray(opts.beta, dtype), (nvecs,)))
    if opts.gamma is not None:
        c = c.at[2].set(jnp.broadcast_to(jnp.asarray(opts.gamma, dtype),
                                         (nvecs,)))
    return c


def as2d(v: jax.Array) -> Tuple[jax.Array, bool]:
    """Promote a single vector to a 1-column block vector.

    Returns ``(v2d, was1d)`` — the shared promotion convention for every
    operator/engine entry point that accepts ``(n,)`` or ``(n, b)``.
    """
    if v.ndim == 1:
        return v[:, None], True
    return v, False


_as2d = as2d


def dot_acc_dtype(dt):
    """Accumulation dtype for the fused dot products (paper: f64 acc).

    64-bit when x64 is enabled (the paper's augmented-SpMV accuracy
    claim); otherwise the widest available float — callers then
    compensate via :func:`compensated_sum0` instead.  Always inexact:
    integer/bool inputs accumulate in float, as the dots are analytic
    quantities (norms, Rayleigh quotients), not counters.
    """
    dt = jnp.dtype(dt)
    x64 = jax.dtypes.canonicalize_dtype(np.float64) == np.dtype(np.float64)
    if jnp.issubdtype(dt, jnp.complexfloating):
        return jnp.dtype(jnp.complex128) if x64 else dt
    if not jnp.issubdtype(dt, jnp.floating):
        return jnp.dtype(jnp.float64 if x64 else jnp.float32)
    if x64:
        return jnp.dtype(jnp.float64)
    return jnp.dtype(jnp.float32) if jnp.finfo(dt).bits < 32 else dt


def compensated_sum0(p: jax.Array, block: int = 256) -> jax.Array:
    """Kahan-compensated sum over axis 0 (the "or Kahan acc" leg).

    Blocks of ``block`` rows are summed pairwise, then the block
    partials are Kahan-accumulated (``blockvec._kahan_reduce``, the same
    compensation the paper's tsmttsm uses), shrinking the uncompensated
    window from ``n`` to ``block`` summands.  The pairwise order is
    spelled out because XLA's own reduce order is unspecified (XLA's CPU
    backend sums sequentially, which loses up to ``block - 1`` half-ulps
    against a spike).  Used for the fused dots when float64 is
    unavailable.
    """
    n = p.shape[0]
    if n == 0:
        return jnp.zeros(p.shape[1:], p.dtype)
    pad = (-n) % block
    if pad:
        p = jnp.pad(p, ((0, pad),) + ((0, 0),) * (p.ndim - 1))
    parts = p.reshape(-1, block, *p.shape[1:])
    while parts.shape[1] > 1:
        if parts.shape[1] % 2:
            parts = jnp.pad(parts, ((0, 0), (0, 1)) +
                            ((0, 0),) * (parts.ndim - 2))
        parts = parts[:, 0::2] + parts[:, 1::2]
    return blockvec._kahan_reduce(parts[:, 0])


def _acc_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """<a, b> per column, accumulated in f64 (or Kahan when x64 is off)."""
    ddt = dot_acc_dtype(jnp.result_type(a.dtype, b.dtype))
    if jnp.finfo(ddt).bits >= 64:              # 64-bit accumulator available
        return jnp.sum(jnp.conj(a.astype(ddt)) * b.astype(ddt), axis=0)
    return blockvec.dot_kahan(a.astype(ddt), b.astype(ddt))


def fused_dots(x2: jax.Array, y2: jax.Array, opts: SpmvOpts) -> jax.Array:
    """The ``(3, b)`` fused-dot bundle ``[<y,y>, <x,y>, <x,x>]``.

    Shared by every operator flavor (``spmv_ref``, the matrix-free hook)
    so the accumulation semantics — conjugated first argument, f64
    accumulation under x64, block-Kahan otherwise — are identical no
    matter which operator a solver runs on.  ``x2``/``y2`` are 2-d
    block vectors; rows not requested by ``opts`` stay zero.
    """
    ddt = dot_acc_dtype(jnp.result_type(y2.dtype, x2.dtype))
    b = y2.shape[1]
    with scope("dots"):
        dots = jnp.zeros((3, b), ddt)
        if opts.dot_yy:
            dots = dots.at[0].set(_acc_dot(y2, y2))
        if opts.dot_xy:
            dots = dots.at[1].set(_acc_dot(x2, y2))
        if opts.dot_xx:
            dots = dots.at[2].set(_acc_dot(x2, x2))
    return dots


def spmv_ref(
    A: SellCS,
    x: jax.Array,
    y: Optional[jax.Array] = None,
    z: Optional[jax.Array] = None,
    opts: SpmvOpts = SpmvOpts(),
):
    """Pure-jnp oracle for the fused SpMV.  Returns (y, z, dots).

    dots is a (3, b) array (rows: yy, xy, xx; zeros where not requested) or
    None if no dot was requested.  z is None unless chaining was requested.
    """
    x2, was1d = _as2d(x)
    n = A.nrows_pad
    if x2.shape[0] != n:
        raise ValueError(
            f"spmv: x must be permuted/padded to {n} rows, got {x2.shape}")
    # accumulate in the matrix' *compute* dtype (== vals dtype for single-
    # dtype matrices — that leg is bit-identical to the classic layout);
    # a narrower store_dtype upcasts per-element before the products
    acc_dt = jnp.result_type(A.dtype, x2.dtype)
    contrib = A.vals.astype(acc_dt)[:, None] * x2.astype(acc_dt)[A.cols]
    Ax = row_sums(contrib, A.rowids, n, A.C, A.uniform_width)

    if opts.gamma is not None:
        gamma = jnp.asarray(opts.gamma)
        Ax = Ax - gamma * x2                          # (A - gamma I) x
    ynew = opts.alpha * Ax
    if y is not None:
        y2, _ = _as2d(y)
        ynew = ynew + opts.beta * jnp.asarray(y2, acc_dt)

    znew = None
    if opts.chain_axpby:
        if z is None:
            raise ValueError("spmv: chained axpby requires z")
        z2, _ = _as2d(z)
        delta = 0.0 if opts.delta is None else opts.delta
        eta = 0.0 if opts.eta is None else opts.eta
        znew = delta * z2 + eta * ynew
        if was1d:
            znew = znew[:, 0]

    dots = None
    if opts.any_dot:
        # f64 accumulation (or Kahan when x64 is off) — the docstring's
        # "f64 or Kahan acc" promise; cast up at this boundary only.
        dots = fused_dots(x2, ynew, opts)

    if was1d:
        ynew = ynew[:, 0]
    return ynew, znew, dots


def row_sums(contrib: jax.Array, rowids: jax.Array, nrows_pad: int,
             C: int, width: int) -> jax.Array:
    """Per-row sums of the per-slot products ``contrib`` (cap, b) of a
    SELL-C-sigma matrix.

    With a uniform chunk ``width`` the slots form a dense
    ``(nchunks, width, C)`` block and the row sums are a reduction over
    its width axis.  Otherwise they are a segment sum over ``rowids``:
    a scatter-add, which a TPU runs element by element.
    """
    if width:
        b = contrib.shape[1]
        return contrib.reshape(nrows_pad // C, width, C, b).sum(
            axis=1).reshape(nrows_pad, b)
    return jax.ops.segment_sum(contrib, rowids, num_segments=nrows_pad)


def spmv(
    A: SellCS,
    x: jax.Array,
    y: Optional[jax.Array] = None,
    z: Optional[jax.Array] = None,
    opts: SpmvOpts = SpmvOpts(),
    *,
    impl: str = "ref",
    interpret: Optional[bool] = None,
):
    """Dispatching fused SpMV (GHOST single-interface ``ghost_spmv``).

    ``interpret=None`` defers to :mod:`repro.core.execution` (compiled on
    TPU, interpret elsewhere, env/context overridable).  Its device work
    is named ``ghost.spmv`` (the fused dots inside it ``ghost.dots``).
    """
    with scope("spmv"):
        if impl == "ref":
            return spmv_ref(A, x, y, z, opts)
        if impl == "pallas":
            from repro.kernels import ops as kops
            return kops.sellcs_spmv(A, x, y, z, opts, interpret=interpret)
    raise ValueError(f"unknown impl {impl!r}")
