"""Pallas TPU kernel: the SELL-128 product ``A x`` with ``x`` held in VMEM.

For a SELL-C-sigma matrix with C = 128 and one width ``W`` shared by
every chunk, ``vals`` and ``cols`` are free views of shape
``(nchunks, W, 128)``: row ``w`` of chunk ``c`` holds slot ``w`` of the
chunk's 128 rows, one per lane.  The kernel keeps ``x.T`` (shape
``(b, n_pad)``, the physical layout XLA gives an ``(n, b)`` solver
vector, so the transpose is free) whole in VMEM.  Its unit of work, a
*step*, pairs one slot row with one column block of 128 rows: it loads
the block's ``(b, 128)`` tile of ``x.T`` (one vreg at b = 8), gathers
across the lanes of that tile with ``cols & 127``
(``tpu.dynamic_gather``), keeps the lanes whose ``cols >> 7`` is this
block, multiplies by the slot's values and accumulates in float32.  A
padding entry (-1) of a list loads block 0 and matches no lane, and the
blocks of one list are distinct, so every stored entry is counted once.
The products and their accumulation dtype are the jnp reference's; only
the order of the row sums differs.

A chunk is walked in one of two layouts, chosen by :func:`plan`:

* ``"slot_rows"``: each slot row ``r`` has its own list of the distinct
  blocks its entries touch, padded to ``U_r``, the longest such list of
  that row over all chunks.  The body is straight-line code of
  ``sum(U_r)`` steps a chunk (11 on a 7-point Poisson matrix, whose
  last slot row is all padding and costs nothing).
* ``"block_rounds"``: each chunk has one list of the distinct blocks of
  all its entries, walked in its own number of rounds of ``U`` blocks
  (``U`` ≤ 8, from the longest list), every slot row against every
  block of a round, unrolled inside a round so their loads and gathers
  overlap: ``W * U`` steps a round (40 a Poisson chunk).

``plan`` counts the steps of both from the lists and takes the smaller
(block rounds on a tie): a few wide chunks raise every chunk's
``U_r``, but only their own rounds.  At b = 1 the kernel walks block
rounds whatever the plan: one gather then serves 8 slot rows, each
sublane gathering from the block broadcast to it.

Which chunks touch which blocks is fixed by the matrix, so the lists are
built once, host-side, by :func:`plan` (vectorised NumPy; padding slots
beyond ``row_len`` are left out).  ``GhostOperator(impl="auto")`` runs
this kernel only where it pays and fits:

* the execution policy is compiled (a TPU backend);
* the matrix is square, real float32 in storage and compute, C = 128,
  with one uniform chunk width;
* the chosen layout's steps per slot row are under
  :data:`BREAK_EVEN_STEPS` (the kernel's work grows with them, the
  gather's does not) and a chunk's list fits one SMEM block;
* at trace time, ``x`` is float32 and ``n_pad * b * 4`` bytes fit
  :data:`X_VMEM_BUDGET` (:func:`fits`).

Everything else runs the jnp product.  The augmentation (alpha/beta,
shift, chained axpby, fused dots) stays in ``core.spmv.augment``,
shared with the reference.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import execution

__all__ = ["WindowPlan", "plan", "fits", "window_product",
           "BREAK_EVEN_STEPS", "X_VMEM_BUDGET"]

C = 128                       # lanes of a vreg: rows of a chunk, a block

#: bytes of ``x.T`` the kernel may keep in VMEM (a v5e core has 128 MiB;
#: the rest holds the streamed vals/cols/y blocks and Mosaic's own use)
X_VMEM_BUDGET = 96 << 20

#: kernel steps per slot row (a chunk's steps over ``W``) from which the
#: jnp gather is the faster path.  The kernel spends one step per (slot
#: row, block) pair it visits; the gather spends one index per slot, 128
#: per slot row.  Measured on a TPU v5e at b = 8 on laplace3d(128): the
#: gather 82.76 ms over 16,777,216 slots, 4.93 ns a slot, 631 ns a slot
#: row; the kernel 4.28 ms over 655,360 block-rounds steps (16,384
#: chunks x 8 slot rows x 5 blocks), 6.53 ns a step.  Break-even:
#: 631 / 6.53 = 96 steps a slot row.  The slot rows' steps are priced
#: as the block rounds' here and in :func:`_layout`: that is assumed,
#: not measured (a slot-rows step runs as straight-line code, with no
#: round of U blocks to overlap its loads and gathers).
BREAK_EVEN_STEPS = 96

#: chunks' worth of vals and cols bytes streamed per grid step, and the
#: int32 list entries one step may hold in SMEM
_STEP_BYTES = 1 << 19
_LIST_ENTRIES = 1 << 13

#: most steps a chunk the slot-rows layout unrolls into straight-line
#: code; a longer walk takes the block rounds, whose loop bounds the
#: code.  A guess: neither the compile time nor the code size of such
#: an unroll has been measured.
_ROW_STEPS_MAX = 256


@dataclasses.dataclass(frozen=True)
class WindowPlan:
    """The column-block lists of one matrix, and the kernel's tiling
    derived from them.

    ``lists`` (the block rounds) is ``(ngroups, 1, G * row)`` int32: for
    each of the ``G`` chunks of a grid step, the number of rounds of
    ``unroll`` blocks it takes, then its block ids, padded with -1 to
    ``row - 1`` entries; chunks past ``nchunks`` in the last group take
    0 rounds.  ``row_lists`` (the slot rows, where that layout is
    chosen, else None) is ``(ngroups, 1, G * steps)`` int32: for each
    chunk, slot row ``r``'s block ids padded with -1 to
    ``row_unroll[r]`` entries, row after row; chunks past ``nchunks``
    hold -1 only.  ``steps`` and ``rounds_steps`` are the mean steps a
    chunk at b > 1 of the chosen layout and of the block rounds.

    Both tables are kept where slot rows are chosen: one operator serves
    b = 1, which walks block rounds, and b > 1, and a traced call embeds
    only the table its width reads.  They share ``G``, so the longer of
    the two rows bounds the SMEM a grid step takes.
    """

    lists: np.ndarray
    width: int                # W, the uniform chunk width
    group: int                # G, chunks per grid step
    unroll: int               # U, blocks a round of the block loop takes
    maxb: int                 # longest list
    mean_blocks: float
    nchunks: int
    row_lists: Optional[np.ndarray] = None
    row_unroll: Tuple[int, ...] = ()      # U_r, per slot row
    steps: float = 0.0
    rounds_steps: float = 0.0

    @property
    def row(self) -> int:
        return 1 + self.unroll * -(-self.maxb // self.unroll)

    @property
    def layout(self) -> str:
        """The walk of a chunk at b > 1 (b = 1 walks block rounds)."""
        return "block_rounds" if self.row_lists is None else "slot_rows"


def _distinct(blk: np.ndarray):
    """``(counts, lists)`` of the distinct non-negative entries of each
    row of ``blk``, ascending, padded with -1 to the longest list."""
    blk = np.sort(blk, axis=1)
    first = np.ones(blk.shape, bool)
    first[:, 1:] = blk[:, 1:] != blk[:, :-1]
    first &= blk >= 0
    counts = first.sum(axis=1).astype(np.int32)
    most = max(int(counts.max(initial=0)), 1)
    lists = np.full((blk.shape[0], most), -1, np.int32)
    r, s = np.nonzero(first)
    lists[r, np.cumsum(first, axis=1)[r, s] - 1] = blk[r, s]
    return counts, lists


def _slot_blocks(cols: np.ndarray, row_len: np.ndarray, width: int):
    """``(nchunks, W, 128)`` column blocks of the stored entries, -1 on
    padding slots."""
    nch = row_len.size // C
    cols = np.asarray(cols, np.int32).reshape(nch, width, C)
    valid = (np.arange(width, dtype=np.int32)[None, :, None]
             < np.asarray(row_len, np.int32).reshape(nch, 1, C))
    return np.where(valid, cols >> 7, -1)


def block_lists(cols: np.ndarray, row_len: np.ndarray, width: int):
    """``(counts, blocks)`` of a uniform-width SELL-128 matrix: the
    distinct column blocks (``col >> 7``) of each chunk's stored
    entries, ascending, padded with -1 to the longest list."""
    blk = _slot_blocks(cols, row_len, width)
    return _distinct(blk.reshape(blk.shape[0], width * C))


def row_block_lists(cols: np.ndarray, row_len: np.ndarray, width: int):
    """``(counts, blocks)``: the distinct column blocks of each slot
    row's stored entries, as ``(nchunks, W)`` counts and ``(nchunks,
    W, most)`` lists padded with -1."""
    blk = _slot_blocks(cols, row_len, width)
    nch = blk.shape[0]
    counts, lists = _distinct(blk.reshape(nch * width, C))
    return counts.reshape(nch, width), lists.reshape(nch, width, -1)


def compiled(interpret: Optional[bool] = None) -> bool:
    """Whether the kernel would run compiled: the policy (or the explicit
    ``interpret``) says compiled, and the backend lowers Pallas."""
    return (not execution.resolve_interpret(interpret)
            and execution.compiled_available())


def plan(A, interpret: Optional[bool] = None) -> Optional[WindowPlan]:
    """The kernel's plan for ``A``, or None where the kernel does not
    apply (see the module docstring); builds the lists of the layout
    with fewer steps."""
    f32 = jnp.dtype(jnp.float32)
    if (not compiled(interpret) or A.C != C
            or not A.uniform_width or A.nrows != A.ncols
            or A.dtype != f32 or A.store_dtype != f32):
        return None
    cols, row_len = np.asarray(A.cols), np.asarray(A.row_len)
    nch, W = A.nchunks, A.uniform_width
    counts, blocks = block_lists(cols, row_len, W)
    mean = float(counts.mean()) if counts.size else 0.0
    maxb = blocks.shape[1]
    # rounds of at most 8 blocks, as even as the longest list allows
    U = -(-maxb // -(-maxb // 8))
    row = 1 + U * -(-maxb // U)
    rounds = -(-counts // U)
    rounds_steps = W * U * float(rounds.mean()) if nch else 0.0
    rcounts, rblocks = row_block_lists(cols, row_len, W)
    unrolls = rcounts.max(axis=0, initial=0)
    S = int(unrolls.sum())
    layout = _layout(S, rounds_steps)
    steps = S if layout == "slot_rows" else rounds_steps
    entries = max(row, S) if layout == "slot_rows" else row
    if steps >= BREAK_EVEN_STEPS * W or entries > _LIST_ENTRIES:
        return None
    G = max(1, min(nch, _STEP_BYTES // (W * C * 8), _LIST_ENTRIES // entries))
    ngroups = -(-nch // G)
    table = np.full((ngroups * G, row), -1, np.int32)
    table[:, 0] = 0
    table[:nch, 0] = rounds
    table[:nch, 1:maxb + 1] = blocks
    row_lists = None
    if layout == "slot_rows":
        # slot row r's first U_r entries, row after row
        take = np.arange(rblocks.shape[2])[None, :] < unrolls[:, None]
        row_lists = np.full((ngroups * G, S), -1, np.int32)
        row_lists[:nch] = rblocks[:, take]
        row_lists = row_lists.reshape(ngroups, 1, -1)
    # host-side: the programs that run the kernel embed them as constants
    return WindowPlan(lists=table.reshape(ngroups, 1, -1),
                      width=W, group=G, unroll=U, maxb=maxb,
                      mean_blocks=mean, nchunks=nch, row_lists=row_lists,
                      row_unroll=tuple(int(u) for u in unrolls),
                      steps=float(steps), rounds_steps=rounds_steps)


def _layout(row_steps: int, rounds_steps: float) -> str:
    """The walk with fewer steps a chunk: slot rows where they take
    fewer than the block rounds and unroll into at most
    :data:`_ROW_STEPS_MAX` steps, else block rounds."""
    return ("slot_rows" if row_steps < rounds_steps
            and row_steps <= _ROW_STEPS_MAX else "block_rounds")


def fits(n_pad: int, b: int, dtype) -> bool:
    """Whether an ``(n_pad, b)`` ``x`` of ``dtype`` can run the kernel:
    float32, and ``x.T`` within :data:`X_VMEM_BUDGET`."""
    return (jnp.dtype(dtype) == jnp.dtype(jnp.float32)
            and n_pad * b * 4 <= X_VMEM_BUDGET)


def _kernel(lists_ref, vals_ref, cols_ref, xt_ref, o_ref, *,
            G: int, W: int, U: int, row: int, b: int, layout: str,
            row_unroll: Tuple[int, ...]):
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

        def load(at):
            # the block at list entry `at`, and its (b, C) tile of x.T;
            # a padding entry (-1) loads block 0 and matches no lane
            blk = lists_ref[0, at]
            at = pl.multiple_of(jnp.maximum(blk, 0) * C, C)
            return blk, xt_ref[:, pl.ds(at, C)]

        def visit(accs, blk, xblk, i):
            # one step: slot-row group i against one block
            gx = jnp.take_along_axis(
                jnp.broadcast_to(xblk, idx[i].shape), idx[i],
                axis=1, mode="promise_in_bounds")[:max(b, groups[i][1])]
            a = 0 if b > 1 else i
            accs[a] = accs[a] + jnp.where(blk_of[i] == blk, v[i], 0.0) * gx

        def blocks(j, accs):
            accs = list(accs)
            for u in range(U):
                blk, xblk = load(base + 1 + j * U + u)
                for i in range(len(groups)):
                    visit(accs, blk, xblk, i)
            return tuple(accs)

        zeros = tuple(jnp.zeros(s, jnp.float32) for s in accs0)
        if layout == "slot_rows":
            accs, at = list(zeros), base
            for i, n in enumerate(row_unroll):
                for _ in range(n):
                    visit(accs, *load(at), i)
                    at += 1
        else:
            accs = lax.fori_loop(0, lists_ref[0, base], blocks, zeros)
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
    rows = b > 1 and p.row_lists is not None
    lists = p.row_lists if rows else p.lists
    ngroups = lists.shape[0]
    vals = A.vals.reshape(p.nchunks, W, C)
    cols = A.cols.reshape(p.nchunks, W, C)
    xt = x.T
    step = G * W * C
    vmem = n_pad * b * 4 + 2 * (2 * step * 4 + b * G * C * 4) + (8 << 20)
    kern = functools.partial(
        _kernel, G=G, W=W, U=p.unroll, b=b,
        row=lists.shape[2] // G,
        layout="slot_rows" if rows else "block_rounds",
        row_unroll=p.row_unroll)
    yt = pl.pallas_call(
        kern,
        grid=(ngroups,),
        in_specs=[
            pl.BlockSpec((None, 1, lists.shape[2]), lambda i: (i, 0, 0),
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
    )(lists, vals, cols, xt)
    return yt.T
