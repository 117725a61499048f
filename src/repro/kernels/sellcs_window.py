"""Pallas TPU kernel: the SELL-128 product ``A x`` with ``x`` held in VMEM.

For a SELL-C-sigma matrix with C = 128 and one width ``W`` shared by
every chunk, ``vals`` and ``cols`` are free views of shape
``(nchunks, W, 128)``: row ``w`` of chunk ``c`` holds slot ``w`` of the
chunk's 128 rows, one per lane.  The kernel keeps ``x.T`` (shape
``(b, n_pad)``, the physical layout XLA gives an ``(n, b)`` solver
vector, so the transpose is free) whole in VMEM and, for every chunk,
walks the column blocks of 128 rows that the chunk touches.  For each
listed block it loads the ``(b, 128)`` tile of ``x.T`` (one vreg at
b = 8) and, for each slot row, gathers across the lanes of that one tile
with ``cols & 127`` (``tpu.dynamic_gather``), keeps the lanes whose
``cols >> 7`` is this block, multiplies by the slot's values and
accumulates in float32.  At b = 1 one gather serves 8 slot rows, each
sublane gathering from the block broadcast to it.  The loop over a
chunk's blocks runs its own number of rounds of ``U`` blocks (``U`` ≤ 8,
from the longest list), unrolled inside a round so their loads and
gathers overlap; a round's padding entries (-1) load block 0 and match
no lane, and the blocks of a chunk are distinct, so every stored entry
is counted once.  The products and their accumulation dtype are the jnp
reference's; only the order of the row sums differs.

Which chunks touch which blocks is fixed by the matrix, so the lists are
built once, host-side, by :func:`plan` (vectorised NumPy; padding slots
beyond ``row_len`` are left out).  ``GhostOperator(impl="auto")`` runs
this kernel only where it pays and fits:

* the execution policy is compiled (a TPU backend);
* the matrix is square, real float32 in storage and compute, C = 128,
  with one uniform chunk width;
* the mean number of blocks per chunk is under
  :data:`BREAK_EVEN_BLOCKS` (the kernel's work grows with it, the
  gather's does not) and the longest list fits one SMEM block;
* at trace time, ``x`` is float32 and ``n_pad * b * 4`` bytes fit
  :data:`X_VMEM_BUDGET` (:func:`fits`).

Everything else runs the jnp product.  The augmentation (alpha/beta,
shift, chained axpby, fused dots) stays in ``core.spmv.augment``,
shared with the reference.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import execution

__all__ = ["WindowPlan", "plan", "fits", "window_product",
           "BREAK_EVEN_BLOCKS", "X_VMEM_BUDGET"]

C = 128                       # lanes of a vreg: rows of a chunk, a block

#: bytes of ``x.T`` the kernel may keep in VMEM (a v5e core has 128 MiB;
#: the rest holds the streamed vals/cols/y blocks and Mosaic's own use)
X_VMEM_BUDGET = 96 << 20

#: mean blocks per chunk from which the jnp gather is the faster path.
#: The kernel spends one step per (slot row, listed block) of a chunk;
#: the gather spends one index per slot, 128 per slot row.  Measured on
#: a TPU v5e at b = 8 on laplace3d(128): the gather 82.76 ms over
#: 16,777,216 slots, 4.93 ns a slot, 631 ns a slot row; the kernel
#: 4.28 ms over 655,360 steps (16,384 chunks x 8 slot rows x 5 blocks),
#: 6.53 ns a step.  Break-even: 631 / 6.53 = 96 blocks per chunk.
BREAK_EVEN_BLOCKS = 96

#: chunks' worth of vals and cols bytes streamed per grid step, and the
#: int32 list entries one step may hold in SMEM
_STEP_BYTES = 1 << 19
_LIST_ENTRIES = 1 << 13


@dataclasses.dataclass(frozen=True)
class WindowPlan:
    """The per-chunk column-block lists of one matrix, and the kernel's
    tiling derived from them.

    ``lists`` is ``(ngroups, 1, G * row)`` int32: for each of the ``G``
    chunks of a grid step, the number of rounds of ``unroll`` blocks it
    takes, then its block ids, padded with -1 to ``row - 1`` entries.
    Chunks past ``nchunks`` in the last group take 0 rounds.
    """

    lists: np.ndarray
    width: int                # W, the uniform chunk width
    group: int                # G, chunks per grid step
    unroll: int               # U, blocks a round of the block loop takes
    maxb: int                 # longest list
    mean_blocks: float
    nchunks: int

    @property
    def row(self) -> int:
        return 1 + self.unroll * -(-self.maxb // self.unroll)


def block_lists(cols: np.ndarray, row_len: np.ndarray, width: int):
    """``(counts, blocks)`` of a uniform-width SELL-128 matrix: the
    distinct column blocks (``col >> 7``) of each chunk's stored
    entries, ascending, padded with -1 to the longest list."""
    nch = row_len.size // C
    cols = np.asarray(cols, np.int32).reshape(nch, width, C)
    valid = (np.arange(width, dtype=np.int32)[None, :, None]
             < np.asarray(row_len, np.int32).reshape(nch, 1, C))
    blk = np.where(valid, cols >> 7, -1).reshape(nch, width * C)
    blk.sort(axis=1)
    first = np.ones(blk.shape, bool)
    first[:, 1:] = blk[:, 1:] != blk[:, :-1]
    first &= blk >= 0
    counts = first.sum(axis=1).astype(np.int32)
    maxb = max(int(counts.max(initial=0)), 1)
    blocks = np.full((nch, maxb), -1, np.int32)
    r, s = np.nonzero(first)
    blocks[r, np.cumsum(first, axis=1)[r, s] - 1] = blk[r, s]
    return counts, blocks


def compiled(interpret: Optional[bool] = None) -> bool:
    """Whether the kernel would run compiled: the policy (or the explicit
    ``interpret``) says compiled, and the backend lowers Pallas."""
    return (not execution.resolve_interpret(interpret)
            and execution.compiled_available())


def plan(A, interpret: Optional[bool] = None) -> Optional[WindowPlan]:
    """The kernel's plan for ``A``, or None where the kernel does not
    apply (see the module docstring); builds the block lists."""
    f32 = jnp.dtype(jnp.float32)
    if (not compiled(interpret) or A.C != C
            or not A.uniform_width or A.nrows != A.ncols
            or A.dtype != f32 or A.store_dtype != f32):
        return None
    counts, blocks = block_lists(np.asarray(A.cols), np.asarray(A.row_len),
                                 A.uniform_width)
    mean = float(counts.mean()) if counts.size else 0.0
    maxb = blocks.shape[1]
    # rounds of at most 8 blocks, as even as the longest list allows
    U = -(-maxb // -(-maxb // 8))
    row = 1 + U * -(-maxb // U)
    if mean >= BREAK_EVEN_BLOCKS or row > _LIST_ENTRIES:
        return None
    nch = A.nchunks
    W = A.uniform_width
    G = max(1, min(nch, _STEP_BYTES // (W * C * 8), _LIST_ENTRIES // row))
    ngroups = -(-nch // G)
    table = np.full((ngroups * G, row), -1, np.int32)
    table[:, 0] = 0
    table[:nch, 0] = -(-counts // U)
    table[:nch, 1:maxb + 1] = blocks
    # host-side: the programs that run the kernel embed it as a constant
    return WindowPlan(lists=table.reshape(ngroups, 1, -1),
                      width=W, group=G, unroll=U, maxb=maxb,
                      mean_blocks=mean, nchunks=nch)


def fits(n_pad: int, b: int, dtype) -> bool:
    """Whether an ``(n_pad, b)`` ``x`` of ``dtype`` can run the kernel:
    float32, and ``x.T`` within :data:`X_VMEM_BUDGET`."""
    return (jnp.dtype(dtype) == jnp.dtype(jnp.float32)
            and n_pad * b * 4 <= X_VMEM_BUDGET)


def _kernel(lists_ref, vals_ref, cols_ref, xt_ref, o_ref, *,
            G: int, W: int, U: int, row: int, b: int):
    # b > 1: one lane gather per slot row, whose b sublanes are the
    # columns of x; b = 1: one per 8 slot rows, each sublane a slot row
    # gathering from the block broadcast to it
    step = 1 if b > 1 else 8
    groups = [(r, min(step, W - r)) for r in range(0, W, step)]
    accs0 = [(b, C)] if b > 1 else [(k, C) for _, k in groups]

    def chunk(g, carry):
        cols = cols_ref[g]                                  # (W, C) int32
        vals = vals_ref[g]                                  # (W, C)
        base = g * row
        idx, blk_of, v = [], [], []
        for r, k in groups:
            shape = (max(b, k, 2), C)          # Mosaic has no 1-row gather
            idx.append(jnp.broadcast_to(cols[r:r + k] & (C - 1), shape))
            blk_of.append(cols[r:r + k] >> 7)
            v.append(vals[r:r + k])

        def blocks(j, accs):
            accs = list(accs)
            for u in range(U):
                blk = lists_ref[0, base + 1 + j * U + u]
                # a padding entry (-1) loads block 0 and matches no lane
                at = pl.multiple_of(jnp.maximum(blk, 0) * C, C)
                xblk = xt_ref[:, pl.ds(at, C)]                # (b, C)
                for i, (r, k) in enumerate(groups):
                    gx = jnp.take_along_axis(
                        jnp.broadcast_to(xblk, idx[i].shape), idx[i],
                        axis=1, mode="promise_in_bounds")[:max(b, k)]
                    a = 0 if b > 1 else i
                    accs[a] = accs[a] + jnp.where(blk_of[i] == blk, v[i],
                                                  0.0) * gx
            return tuple(accs)

        accs = lax.fori_loop(0, lists_ref[0, base], blocks,
                             tuple(jnp.zeros(s, jnp.float32) for s in accs0))
        acc = (accs[0] if b > 1 else
               sum(a.sum(axis=0, keepdims=True) for a in accs))
        o_ref[:, pl.ds(pl.multiple_of(g * C, C), C)] = acc
        return carry

    lax.fori_loop(0, G, chunk, 0)


def window_product(A, x: jax.Array, p: WindowPlan, *,
                   interpret: Optional[bool] = None) -> jax.Array:
    """``A @ x`` for an ``(n_pad, b)`` float32 ``x``, as ``(n_pad, b)``
    float32.  ``interpret=None`` defers to :mod:`repro.core.execution`."""
    interpret = execution.resolve_interpret(interpret)
    if x.ndim != 2 or x.shape[0] != p.nchunks * C or not fits(*x.shape,
                                                              x.dtype):
        raise ValueError(
            f"window_product: x must be float32 (n_pad={p.nchunks * C}, b) "
            f"within {X_VMEM_BUDGET} bytes, got {x.dtype}{tuple(x.shape)}")
    if A.uniform_width != p.width or A.nchunks != p.nchunks:
        raise ValueError("window_product: the plan was built for another "
                         "matrix")
    n_pad, b = x.shape
    W, G = p.width, p.group
    ngroups = p.lists.shape[0]
    vals = A.vals.reshape(p.nchunks, W, C)
    cols = A.cols.reshape(p.nchunks, W, C)
    xt = x.T
    step = G * W * C
    vmem = n_pad * b * 4 + 2 * (2 * step * 4 + b * G * C * 4) + (8 << 20)
    kern = functools.partial(_kernel, G=G, W=W, U=p.unroll, row=p.row, b=b)
    yt = pl.pallas_call(
        kern,
        grid=(ngroups,),
        in_specs=[
            pl.BlockSpec((None, 1, p.lists.shape[2]), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((G, W, C), lambda i: (i, 0, 0)),
            pl.BlockSpec((G, W, C), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((b, G * C), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, n_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(p.lists, vals, cols, xt)
    return yt.T
