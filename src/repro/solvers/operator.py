"""Linear operator abstraction for the solvers.

``GhostOperator`` wraps a SELL-C-sigma matrix and exposes the fused
augmented SpM(M)V; ``MatrixFreeOperator`` is the paper's function-pointer
hook (section 5.1: "a user can replace this function pointer by a custom
function that performs the SpMV in any (possibly matrix-free) way");
``DistOperator`` runs the matvec on the heterogeneous execution engine
(:class:`repro.runtime.engine.HeterogeneousEngine`) so the same solvers
scale out over a device mesh with task-mode overlap.

All solver vectors live in the operator's *permuted* space with shape
``(n, b)`` (block vectors); use :meth:`to_op_space` / :meth:`from_op_space`
at the boundaries.  For ``DistOperator`` the operator space is the
flattened stack of shard-local slices (``n = nshards * m_pad``); padding
slots are kept at zero so norms and dot products are exact.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sellcs import SellCS
from repro.core.spmv import SpmvOpts, as2d, fused_dots, pack_coefs, spmv
from repro.kernels import sellcs_window


class GhostOperator:
    """A SELL-C-sigma matrix as a solver operator.

    ``impl="auto"`` resolves once, here, to ``"window"`` (the Pallas
    kernel of :mod:`repro.kernels.sellcs_window`, whose block lists are
    built now) where that kernel qualifies for ``A``, else to ``"ref"``;
    ``"ref"`` and ``"pallas"`` are taken as given.  ``window`` holds the
    kernel's plan, or None.
    """

    def __init__(self, A: SellCS, *, impl: str = "auto",
                 interpret: Optional[bool] = None):
        # interpret=None defers to repro.core.execution at call time, so
        # the operator follows `execution.force(...)` scopes automatically
        self.A = A
        self.window = None
        if impl == "auto":
            self.window = sellcs_window.plan(A, interpret)
            impl = "ref" if self.window is None else "window"
        self.impl = impl
        self.interpret = interpret
        self.n = A.nrows_pad
        # solver vectors/states live in the *compute* dtype; a narrower
        # store_dtype only changes what the kernels stream from memory
        self.dtype = A.dtype
        self.store_dtype = A.store_dtype

    def mv(self, x: jax.Array) -> jax.Array:
        y, _, _ = spmv(self.A, x, impl=self.impl, interpret=self.interpret,
                       window=self.window)
        return y

    def mv_fused(self, x, y=None, z=None, opts: SpmvOpts = SpmvOpts()):
        return spmv(self.A, x, y, z, opts, impl=self.impl,
                    interpret=self.interpret, window=self.window)

    def to_op_space(self, v):
        return self.A.permute(v)

    def from_op_space(self, v):
        return self.A.unpermute(v)


class MatrixFreeOperator:
    """Matrix-free SpMV hook (paper section 5.1)."""

    def __init__(self, fn: Callable[[jax.Array], jax.Array], n: int, dtype):
        self.fn = fn
        self.n = n
        self.dtype = jnp.dtype(dtype)

    def mv(self, x):
        return self.fn(x)

    def mv_fused(self, x, y=None, z=None, opts: SpmvOpts = SpmvOpts()):
        Ax = self.fn(x)
        if opts.gamma is not None:
            Ax = Ax - jnp.asarray(opts.gamma) * x
        ynew = opts.alpha * Ax
        if y is not None:
            ynew = ynew + opts.beta * y
        znew = None
        if opts.chain_axpby:
            if z is None:
                raise ValueError(
                    "SpmvOpts requested a chained AXPBY (delta/eta set) but "
                    "no z vector was passed to mv_fused")
            delta = 0.0 if opts.delta is None else opts.delta
            eta = 0.0 if opts.eta is None else opts.eta
            znew = delta * z + eta * ynew
        dots = None
        if opts.any_dot:
            # same widened/compensated (and conjugated) accumulation as
            # spmv_ref — a matrix-free swap must not change solver numerics
            dots = fused_dots(as2d(x)[0], as2d(ynew)[0], opts)
        return ynew, znew, dots

    def to_op_space(self, v):
        return v

    def from_op_space(self, v):
        return v


class DistOperator:
    """Distributed operator over a :class:`HeterogeneousEngine`.

    Solver vectors are the flattened shard stack ``(nshards * m_pad, b)``.
    Inputs are masked to the valid (non-padding) slots on entry and the
    matvec keeps padding at zero, so the solvers' dot products and norms
    see exactly the original operator embedded in a zero block.  Build
    right-hand sides with :meth:`to_op_space` (global -> operator space).

    Matrix state is read through the engine on every access, so the
    operator follows ``engine.rebalance()`` automatically.  NOTE that a
    rebalance changes the operator-space *layout* (and possibly ``n``):
    vectors built before it are stale — round-trip them through
    ``from_op_space`` before / ``to_op_space`` after the rebalance.
    """

    def __init__(self, engine, *, overlap: bool = True, impl: str = "ref",
                 interpret: Optional[bool] = None):
        self.engine = engine
        self.overlap = overlap
        # the shards take an explicit executor; "auto" is the jnp product
        self.impl = "ref" if impl == "auto" else impl
        self.interpret = interpret
        self._mask_cache = (None, None)     # (A object, its mask)

    # ------------------------------------------------------------ helpers
    @property
    def A(self):
        return self.engine.A

    @property
    def n(self) -> int:
        return self.A.nshards * self.A.m_pad

    @property
    def dtype(self):
        # compute dtype: what solver vectors and dot products use.  The
        # value shards themselves may be stored narrower (store_dtype).
        return self.A.dtype

    @property
    def store_dtype(self):
        return self.A.store_dtype

    @property
    def _mask(self):
        # (n, 1) validity mask: g2l == -1 marks padding slots.  Built
        # host-side (numpy) so it is a concrete constant even when first
        # touched under a jit trace — caching a traced value here would
        # leak the tracer into later calls.
        A = self.A
        key, mask = self._mask_cache
        if key is not A:
            host = (np.asarray(A.g2l) >= 0).reshape(self.n, 1)
            mask = jnp.asarray(host.astype(np.dtype(self.dtype)))
            if not isinstance(mask, jax.core.Tracer):
                self._mask_cache = (A, mask)
        return mask

    def _stack(self, v):
        return v.reshape(self.A.nshards, self.A.m_pad, v.shape[1])

    def _flat(self, v):
        return v.reshape(self.n, v.shape[2])

    def _apply(self, x, y, opts: SpmvOpts):
        x2, was1d = as2d(x)
        x2 = x2 * self._mask
        y2 = None
        if y is not None:
            y2 = as2d(y)[0] * self._mask
        nvecs = x2.shape[1]
        run = self.engine.make_matvec(
            overlap=self.overlap, impl=self.impl, interpret=self.interpret,
            nvecs=nvecs, with_y=y is not None, dot_yy=opts.dot_yy,
            dot_xy=opts.dot_xy, dot_xx=opts.dot_xx,
            has_gamma=opts.gamma is not None)
        coefs = pack_coefs(opts, nvecs, self.dtype)
        ys, dots, _ = run(self._stack(x2),
                          self._stack(y2) if y2 is not None else None, coefs)
        out = self._flat(ys)
        if was1d:
            out = out[:, 0]
        return out, dots

    # ---------------------------------------------------------- operator API
    def mv(self, x: jax.Array) -> jax.Array:
        y, _ = self._apply(x, None, SpmvOpts())
        return y

    def mv_fused(self, x, y=None, z=None, opts: SpmvOpts = SpmvOpts()):
        ynew, dots = self._apply(x, y, opts)
        znew = None
        if opts.chain_axpby:
            if z is None:
                raise ValueError("chained axpby requires z")
            delta = 0.0 if opts.delta is None else opts.delta
            eta = 0.0 if opts.eta is None else opts.eta
            znew = delta * z + eta * ynew
        return ynew, znew, dots

    def to_op_space(self, v):
        v2, was1d = as2d(v)
        out = self._flat(self.A.distribute_vec(v2))
        return out[:, 0] if was1d else out

    def from_op_space(self, v):
        v2, was1d = as2d(v)
        out = self.A.collect_vec(self._stack(v2))
        return out[:, 0] if was1d else out


def make_operator(A, **kw):
    if isinstance(A, SellCS):
        return GhostOperator(A, **kw)
    from repro.runtime.engine import HeterogeneousEngine
    if isinstance(A, HeterogeneousEngine):
        return DistOperator(A, **kw)
    raise TypeError(f"cannot wrap {type(A)}")
