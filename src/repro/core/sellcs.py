"""SELL-C-sigma sparse matrix storage (paper C1).

The central data structure of GHOST.  A sparse matrix is cut into chunks of
``C`` rows (``C`` = SIMD/lane width — 128 matches the TPU VPU lane count,
but any value works; the Pallas kernel additionally tiles each chunk's
width in ``w_tile`` slabs, so chunk widths are padded to a multiple of
``w_align`` at construction time — pick ``w_align`` = the ``w_tile`` you
intend to run with).  Within a *sorting window* of ``sigma`` rows, rows are
sorted by descending nonzero count before chunk assembly, which minimizes
the zero-padding ``beta`` overhead.  Chunk entries are stored column-major
within the chunk so that one contiguous load feeds all C lanes.

**Storage vs compute dtype** (paper C6 over data types): SpMV is memory-
bandwidth-bound, so the value stream may be narrower than the arithmetic.
``store_dtype=`` keeps ``vals`` in ``bfloat16``/``float16``/``float32``
while the recorded ``compute_dtype`` (the ``dtype=`` argument) drives
every accumulation — kernels upcast the value tile in-register and the
accumulator stays f32/f64.  ``store_dtype=None`` (the default) keeps
``vals`` in the compute dtype, bit-identical to the single-dtype layout.
See ``docs/mixed_precision.md`` for the full contract.

Special cases (paper section 5.1):
    SELL-1-1          == CRS
    SELL-C-1          == unsorted SELL-C
    SELL-nrows-nrows  == (globally sorted) ELLPACK-ish
    SELL-C-sigma      == general case

Vectors are kept in *permuted* space (like GHOST, which permutes matrix
columns along with the rows); use :meth:`SellCS.permute` /
:meth:`SellCS.unpermute` at the boundaries.  For square matrices the column
indices are remapped through the inverse permutation at construction time so
that SpMV never needs to gather through the permutation.

Construction is host-side numpy (the paper constructs via a user callback on
the host as well); the result is a JAX pytree usable inside jit.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SellCS",
    "from_coo",
    "from_csr",
    "from_dense",
    "from_callback",
    "to_dense",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SellCS:
    """SELL-C-sigma matrix.  Arrays are a JAX pytree; sizes are static."""

    # --- array leaves -----------------------------------------------------
    vals: jax.Array        # (cap,) chunk-column-major nonzero values (padded)
    cols: jax.Array        # (cap,) int32 column indices (permuted space)
    chunk_off: jax.Array   # (nchunks,) int32, chunk c spans vals[off*C:(off+len)*C]
    chunk_len: jax.Array   # (nchunks,) int32 padded width of chunk c
    rowids: jax.Array      # (cap,) int32 row id (permuted space) per slot; for ref path
    row_len: jax.Array     # (nrows_pad,) int32 stored entries per permuted row
    perm: jax.Array        # (nrows_pad,) int32 sorted-pos -> original row
    iperm: jax.Array       # (nrows_pad,) int32 original row -> sorted-pos

    # --- static metadata ---------------------------------------------------
    C: int = dataclasses.field(metadata=dict(static=True))
    sigma: int = dataclasses.field(metadata=dict(static=True))
    nrows: int = dataclasses.field(metadata=dict(static=True))
    ncols: int = dataclasses.field(metadata=dict(static=True))
    nnz: int = dataclasses.field(metadata=dict(static=True))
    w_align: int = dataclasses.field(metadata=dict(static=True))
    permuted_cols: bool = dataclasses.field(metadata=dict(static=True))
    # compute (accumulation) dtype name when ``vals`` is stored narrower;
    # None = vals *are* the compute dtype (the classic single-dtype layout)
    compute_dtype: Optional[str] = dataclasses.field(
        default=None, metadata=dict(static=True))
    # the padded width shared by every chunk, when all are equal (the
    # slots then form a dense (nchunks, width, C) block); 0 = ragged
    uniform_width: int = dataclasses.field(
        default=0, metadata=dict(static=True))

    # ------------------------------------------------------------------ api
    @property
    def nchunks(self) -> int:
        return (self.nrows_pad // self.C)

    @property
    def nrows_pad(self) -> int:
        return _ceil_to(self.nrows, self.C)

    @property
    def cap(self) -> int:
        return int(self.vals.shape[0])

    @property
    def beta(self) -> float:
        """Storage efficiency: nnz / padded slots (paper's beta)."""
        return self.nnz / max(1, self.cap)

    @property
    def dtype(self):
        """The *compute* dtype: what SpMV products accumulate in and what
        every solver vector should use.  Equals ``store_dtype`` unless the
        matrix was built with a narrower ``store_dtype=``."""
        if self.compute_dtype is not None:
            return jnp.dtype(self.compute_dtype)
        return self.vals.dtype

    @property
    def store_dtype(self):
        """The *storage* dtype of ``vals`` (the memory-traffic dtype)."""
        return self.vals.dtype

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.nrows, self.ncols)

    # -- vector permutation boundary helpers (paper: vectors live permuted) --
    def permute(self, v: jax.Array) -> jax.Array:
        """Original-space vector -> permuted (sorted) space, padded to nrows_pad."""
        v = jnp.asarray(v)
        pad = self.nrows_pad - self.nrows
        if v.ndim == 1:
            vp = jnp.pad(v, (0, pad))
        else:
            vp = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        return vp[self.perm]

    def unpermute(self, v: jax.Array) -> jax.Array:
        """Permuted-space (padded) vector -> original space (trimmed)."""
        return v[self.iperm][: self.nrows]

    def nnz_per_row(self) -> np.ndarray:
        """Stored entries per permuted-space row.

        Derived from the per-row lengths recorded at construction — NOT
        from ``vals != 0``, so explicitly stored zeros (and duplicates
        that summed to 0.0) are counted.
        """
        return np.asarray(self.row_len, np.int64).copy()

    def valid_slots(self) -> np.ndarray:
        """Boolean (cap,) mask of slots holding a stored entry (host-side).

        Slot validity comes from the construction-recorded row lengths:
        slot ``(chunk_off[c] + k) * C + lane`` is valid iff
        ``k < row_len[c*C + lane]``.  Padding slots carry ``vals == 0``
        too, but the converse does not hold for explicitly stored zeros.
        """
        co = np.asarray(self.chunk_off, np.int64)
        rid = np.asarray(self.rowids, np.int64)
        slot = np.arange(self.cap, dtype=np.int64)
        k = slot // self.C - co[rid // self.C]
        return k < np.asarray(self.row_len, np.int64)[rid]


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------

def from_coo(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    shape: Tuple[int, int],
    *,
    C: int = 32,
    sigma: int = 1,
    w_align: int = 1,
    dtype=None,
    store_dtype=None,
    row_perm: Optional[np.ndarray] = None,
    permute_columns: Optional[bool] = None,
) -> SellCS:
    """Build a SELL-C-sigma matrix from COO triplets (host-side).

    ``sigma`` must be a multiple of ``C`` (or 1).  ``w_align`` pads every
    chunk width to a multiple, which the Pallas kernel uses for its width
    tiling (trades a little beta for aligned slab loads).

    ``dtype`` is the **compute** dtype (accumulation, vectors, results);
    ``store_dtype`` optionally stores ``vals`` narrower (``bfloat16`` /
    ``float16`` / ``float32``) to halve the SpMV value traffic — kernels
    upcast in-register and accumulate in the compute dtype, so ``dtype``
    semantics are unchanged.  ``store_dtype=None`` keeps ``vals`` in the
    compute dtype, bit-identical to the pre-mixed-precision layout.

    ``row_perm`` imposes an externally chosen row permutation (sorted-pos ->
    original row, length nrows_pad) instead of sigma-sorting — used by the
    distributed layer so the remote matrix part shares the local part's
    permutation.  ``permute_columns`` overrides the default column remapping
    (default: remap iff the matrix is square and no external perm is given).
    """
    nrows, ncols = map(int, shape)
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals)
    if dtype is not None:
        vals = vals.astype(dtype)
    if sigma != 1 and sigma % C != 0:
        raise ValueError(f"sigma ({sigma}) must be 1 or a multiple of C ({C})")
    if rows.size:
        if rows.min() < 0 or rows.max() >= nrows:
            raise ValueError("row index out of range")
        if cols.min() < 0 or cols.max() >= ncols:
            raise ValueError("col index out of range")

    # CSR-ify (sorted, deduplicated by summation like most sparse builders)
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    if rows.size:
        dup = np.zeros(rows.size, bool)
        dup[1:] = (rows[1:] == rows[:-1]) & (cols[1:] == cols[:-1])
        if dup.any():
            # sum duplicates
            keep = ~dup
            grp = np.cumsum(keep) - 1
            nv = np.zeros(keep.sum(), vals.dtype)
            np.add.at(nv, grp, vals)
            rows, cols, vals = rows[keep], cols[keep], nv
    nnz = int(rows.size)

    nrows_pad = _ceil_to(nrows, C)
    rowlen = np.zeros(nrows_pad, np.int64)
    np.add.at(rowlen, rows, 1)

    # --- sigma sorting: stable descending rowlen within each window --------
    if row_perm is not None:
        perm = np.asarray(row_perm, np.int64)
        if perm.shape != (nrows_pad,):
            raise ValueError(f"row_perm must have shape ({nrows_pad},)")
    else:
        perm = np.arange(nrows_pad, dtype=np.int64)
        if sigma > 1:
            win = sigma
            for s in range(0, nrows_pad, win):
                e = min(s + win, nrows_pad)
                seg = np.argsort(-rowlen[s:e], kind="stable") + s
                perm[s:e] = seg
    iperm = np.empty_like(perm)
    iperm[perm] = np.arange(nrows_pad)

    sorted_rowlen = rowlen[perm]

    nchunks = nrows_pad // C
    chunk_len = np.zeros(nchunks, np.int64)
    for c in range(nchunks):
        w = int(sorted_rowlen[c * C : (c + 1) * C].max(initial=0))
        chunk_len[c] = _ceil_to(max(w, 1), w_align)
    chunk_off = np.zeros(nchunks, np.int64)
    chunk_off[1:] = np.cumsum(chunk_len)[:-1]
    cap = int(chunk_len.sum()) * C

    # --- scatter CSR rows into chunk-column-major slots ---------------------
    out_vals = np.zeros(cap, vals.dtype if vals.size else np.float32)
    out_cols = np.zeros(cap, np.int64)
    out_rowid = np.zeros(cap, np.int64)
    # slot index for element k of (sorted) row s in chunk c:
    #   (chunk_off[c] + k) * C + (s - c*C)
    if nnz:
        sorted_pos = iperm[rows]              # per-nnz sorted row position
        chunk_of = sorted_pos // C
        lane = sorted_pos % C
        # k = running index within the row (rows are contiguous post-lexsort)
        starts = np.concatenate([[0], np.cumsum(rowlen[:nrows])[:-1]])
        k = np.arange(nnz, dtype=np.int64) - starts[rows]
        slot = (chunk_off[chunk_of] + k) * C + lane
        out_vals[slot] = vals
        out_cols[slot] = cols
    # rowids for every slot (padding slots get their row too, with val 0)
    slot_all = np.arange(cap, dtype=np.int64)
    # invert: which chunk does a slot belong to
    chunk_bounds = (chunk_off + chunk_len) * C
    chunk_of_slot = np.searchsorted(chunk_bounds, slot_all, side="right")
    lane_of_slot = (slot_all - chunk_off[chunk_of_slot] * C) % C
    out_rowid = chunk_of_slot * C + lane_of_slot

    # permuted column space for square matrices: col j -> iperm[j].
    # Validity is the slot occupancy recorded above — NOT ``vals != 0``,
    # which would skip explicitly stored zeros (their column must be
    # remapped too so structure round-trips through to_dense).
    if permute_columns is None:
        permuted_cols = (nrows == ncols) and row_perm is None
    else:
        permuted_cols = bool(permute_columns)
    if permuted_cols and nnz:
        valid_slot = np.zeros(cap, bool)
        valid_slot[slot] = True
        out_cols_p = out_cols.copy()
        out_cols_p[valid_slot] = iperm[out_cols[valid_slot]]
        out_cols = out_cols_p

    jvals = jnp.asarray(out_vals)               # canonicalized compute dtype
    compute_dtype = None
    if store_dtype is not None:
        sd = jnp.dtype(store_dtype)
        cd = jvals.dtype
        if not jnp.issubdtype(sd, jnp.floating):
            raise ValueError(
                f"store_dtype must be a real floating dtype, got {sd}")
        if jnp.issubdtype(cd, jnp.complexfloating):
            raise ValueError(
                f"store_dtype is not supported for complex values "
                f"(compute dtype {cd})")
        if not jnp.issubdtype(cd, jnp.floating):
            raise ValueError(
                f"store_dtype requires a floating compute dtype, got {cd}; "
                f"pass dtype= (float values would stream from storage into "
                f"integer solver states otherwise)")
        if jnp.finfo(sd).bits > jnp.finfo(cd).bits:
            raise ValueError(
                f"store_dtype {sd} is wider than the compute dtype {cd}; "
                f"storage may only narrow the value stream")
        compute_dtype = str(cd)
        jvals = jvals.astype(sd)

    return SellCS(
        vals=jvals,
        cols=jnp.asarray(out_cols, jnp.int32),
        chunk_off=jnp.asarray(chunk_off, jnp.int32),
        chunk_len=jnp.asarray(chunk_len, jnp.int32),
        rowids=jnp.asarray(out_rowid, jnp.int32),
        row_len=jnp.asarray(sorted_rowlen, jnp.int32),
        perm=jnp.asarray(perm, jnp.int32),
        iperm=jnp.asarray(iperm, jnp.int32),
        C=int(C),
        sigma=int(sigma),
        nrows=nrows,
        ncols=ncols,
        nnz=nnz,
        w_align=int(w_align),
        permuted_cols=bool(permuted_cols),
        compute_dtype=compute_dtype,
        uniform_width=_uniform_width(chunk_len),
    )


def _uniform_width(chunk_len) -> int:
    """The width every chunk shares, or 0 if the widths differ."""
    chunk_len = np.asarray(chunk_len)
    if chunk_len.size and (chunk_len == chunk_len[0]).all():
        return int(chunk_len[0])
    return 0


def from_csr(indptr, indices, data, shape, **kw) -> SellCS:
    """Paper section 5.1: construct SELL-C-sigma from raw CRS arrays."""
    indptr = np.asarray(indptr, np.int64)
    rows = np.repeat(np.arange(len(indptr) - 1, dtype=np.int64), np.diff(indptr))
    return from_coo(rows, np.asarray(indices), np.asarray(data), shape, **kw)


def from_dense(a: np.ndarray, **kw) -> SellCS:
    a = np.asarray(a)
    r, c = np.nonzero(a)
    return from_coo(r, c, a[r, c], a.shape, **kw)


def from_callback(
    rowfunc: Callable[[int], Tuple[np.ndarray, np.ndarray]],
    nrows: int,
    ncols: Optional[int] = None,
    *,
    maxnz_per_row: int = 64,
    **kw,
) -> SellCS:
    """GHOST's preferred construction path: a per-row callback.

    ``rowfunc(i) -> (cols, vals)`` mirrors the paper's
    ``int mat(row, *len, *col, *val, *arg)`` C callback.
    """
    ncols = nrows if ncols is None else ncols
    rr, cc, vv = [], [], []
    for i in range(nrows):
        c, v = rowfunc(i)
        c = np.asarray(c, np.int64).ravel()
        v = np.asarray(v).ravel()
        if c.size > maxnz_per_row:
            raise ValueError(f"row {i}: {c.size} > maxnz_per_row={maxnz_per_row}")
        rr.append(np.full(c.size, i, np.int64))
        cc.append(c)
        vv.append(v)
    rows = np.concatenate(rr) if rr else np.zeros(0, np.int64)
    cols = np.concatenate(cc) if cc else np.zeros(0, np.int64)
    vals = np.concatenate(vv) if vv else np.zeros(0)
    return from_coo(rows, cols, vals, (nrows, ncols), **kw)


def to_dense(m: SellCS) -> np.ndarray:
    """Densify (original index space) — for tests / small matrices only.

    Slot validity comes from the construction-recorded row lengths
    (:meth:`SellCS.valid_slots`), so explicitly stored zeros keep their
    (correctly remapped) position instead of being treated as padding.
    Values are returned in the *compute* dtype (upcast from a narrower
    ``store_dtype`` storage; a no-op for single-dtype matrices).
    """
    vals = np.asarray(m.vals).astype(np.dtype(m.dtype))
    cols = np.asarray(m.cols)
    rowid = np.asarray(m.rowids)
    perm = np.asarray(m.perm)
    out = np.zeros((m.nrows_pad, m.ncols), vals.dtype)
    mask = m.valid_slots()
    r_orig = perm[rowid[mask]]
    c = cols[mask]
    if m.permuted_cols:
        c = perm[c]
    np.add.at(out, (r_orig, c), vals[mask])
    return out[: m.nrows]
