"""Distributed SELL-C-sigma SpMV (paper C4 + C5).

Row-wise, *weight-proportional* distribution of the system matrix across a
device mesh axis (GHOST section 4.1, Fig. 3), with the process-local matrix
split into a **local** part (columns owned by this shard) and a **remote**
part whose column indices are *compressed* into a dense halo buffer —
exactly the paper's remote-column compression, which on TPU doubles as the
trick that keeps the remote gather inside a small VMEM-resident buffer.

Communication is a static-pattern halo exchange realised with
``lax.all_to_all`` (pairwise send lists precomputed host-side, padded to the
maximum message size).  The *task-mode* overlap of GHOST (section 4.2) maps
to TPU as data-flow independence: the local SpMV consumes only ``x_local``
while the halo exchange runs, so XLA's async collective scheduler can
overlap them; ``overlap=False`` inserts an optimization barrier to force the
paper's "No Overlap" baseline for the Fig. 5 study.

Everything here is pure SPMD ``shard_map`` — the same code lowers to the
16x16 pod mesh and the 2x16x16 multi-pod mesh in the dry-run.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import execution
from repro.core import partition as part
from repro.core.sellcs import SellCS, _uniform_width, from_coo
from repro.core.spmv import SpmvOpts, row_sums, spmv_ref

__all__ = [
    "DistSellCS", "dist_from_coo", "dist_spmv", "make_dist_spmv",
    # pipeline stages (recomposed by repro.runtime.pipeline)
    "halo_pack", "halo_exchange_unpack", "local_stage", "remote_stage",
    "fused_epilogue", "spmv_shard_stages", "dist_spmv_shard",
]


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DistSellCS:
    """Row-distributed SELL-C-sigma matrix over ``nshards`` shards.

    All per-shard arrays are stacked on a leading shard axis and padded to
    the max over shards so they form one shardable global array.
    """

    # local part (square, shard-sigma-permuted cols), stacked + padded
    l_vals: jax.Array      # (P, capL)
    l_cols: jax.Array      # (P, capL)
    l_off: jax.Array       # (P, ncks)
    l_len: jax.Array       # (P, ncks)
    l_rowids: jax.Array    # (P, capL)
    # remote part (cols index the halo buffer), same row perm as local
    r_vals: jax.Array      # (P, capR)
    r_cols: jax.Array      # (P, capR)
    r_off: jax.Array       # (P, ncks)
    r_len: jax.Array       # (P, ncks)
    r_rowids: jax.Array    # (P, capR)
    # halo exchange maps
    send_idx: jax.Array    # (P, P, max_msg) gather into x_local
    halo_idx: jax.Array    # (P, H_max) gather into flattened recv buffer
    # vector distribution maps
    g2l: jax.Array         # (P, m_pad) original global row per local slot (-1 pad)
    pos_of_global: jax.Array  # (nrows,) into flattened (P*m_pad)

    # partition bookkeeping (host-side; feeds the runtime's rebalance loop)
    row_ranges: Tuple[Tuple[int, int], ...] = dataclasses.field(
        metadata=dict(static=True))
    shard_nnz: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))

    # statics
    nshards: int = dataclasses.field(metadata=dict(static=True))
    C: int = dataclasses.field(metadata=dict(static=True))
    sigma: int = dataclasses.field(metadata=dict(static=True))
    w_align: int = dataclasses.field(metadata=dict(static=True))
    nrows: int = dataclasses.field(metadata=dict(static=True))
    m_pad: int = dataclasses.field(metadata=dict(static=True))
    max_msg: int = dataclasses.field(metadata=dict(static=True))
    h_max: int = dataclasses.field(metadata=dict(static=True))
    # compute (accumulation) dtype name when the value shards are stored
    # narrower; None = values are stored in the compute dtype
    compute_dtype: Optional[str] = dataclasses.field(
        default=None, metadata=dict(static=True))
    # chunk width shared by every shard's local / remote part when the
    # stacked slots form a dense (ncks, width, C) block per shard; 0 =
    # ragged (see SellCS.uniform_width)
    l_width: int = dataclasses.field(default=0, metadata=dict(static=True))
    r_width: int = dataclasses.field(default=0, metadata=dict(static=True))

    # ------------------------------------------------------------------
    @property
    def dtype(self):
        """Compute dtype — accumulation, vectors, halo buffers."""
        if self.compute_dtype is not None:
            return jnp.dtype(self.compute_dtype)
        return self.l_vals.dtype

    @property
    def store_dtype(self):
        """Storage dtype of the local/remote value shards (HBM traffic)."""
        return self.l_vals.dtype

    @property
    def comm_volume(self) -> int:
        """Worst-case halo words moved per shard per SpMV (padded)."""
        return self.nshards * self.max_msg

    def distribute_vec(self, x: jax.Array) -> jax.Array:
        """Global original-space (nrows[, b]) -> stacked shard-local (P, m_pad[, b])."""
        idx = jnp.clip(self.g2l, 0, self.nrows - 1)
        mask = (self.g2l >= 0)
        xv = x[idx]
        if x.ndim > 1:
            mask = mask[..., None]
        return jnp.where(mask, xv, 0)

    def collect_vec(self, xs: jax.Array) -> jax.Array:
        """Stacked shard-local (P, m_pad[, b]) -> global (nrows[, b])."""
        flat = xs.reshape((self.nshards * self.m_pad,) + xs.shape[2:])
        return flat[self.pos_of_global]


def dist_from_coo(
    rows, cols, vals, nrows: int, *,
    nshards: int,
    weights: Optional[Sequence[float]] = None,
    C: int = 32,
    sigma: int = 1,
    w_align: int = 1,
    by_nnz: bool = False,
    dtype=None,
    store_dtype=None,
    ranges: Optional[Sequence[Tuple[int, int]]] = None,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
) -> DistSellCS:
    """Build a row-distributed SELL-C-sigma matrix from global COO (square).

    ``ranges`` overrides the internal weighted partition with precomputed
    contiguous row ranges (e.g. from :func:`repro.runtime.split.plan_split`,
    which produces C-aligned, non-empty, apportionment-balanced shards).

    ``store_dtype`` keeps every shard's local *and* remote value arrays in
    a narrower storage dtype end-to-end (the halo exchange itself moves
    vector data in the compute ``dtype``; only matrix values narrow) —
    see :func:`repro.core.sellcs.from_coo`.

    With ``mesh``, each shard's slice of the stacked per-shard arrays is
    placed on its own device of mesh axis ``axis``; without it they land
    on the default device.
    """
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals)
    if dtype is not None:
        vals = vals.astype(dtype)
    weights = [1.0] * nshards if weights is None else list(weights)
    if len(weights) != nshards:
        raise ValueError(
            f"expected {nshards} weights, got {len(weights)}")

    if ranges is not None:
        ranges = [(int(s), int(e)) for (s, e) in ranges]
        if len(ranges) != nshards:
            raise ValueError(
                f"expected {nshards} ranges, got {len(ranges)}")
        if ranges[0][0] != 0 or ranges[-1][1] != nrows:
            raise ValueError(
                f"ranges must cover [0, {nrows}), got "
                f"[{ranges[0][0]}, {ranges[-1][1]})")
        if any(ranges[i][1] != ranges[i + 1][0]
               for i in range(nshards - 1)):
            raise ValueError("ranges must be contiguous (each end == "
                             "next start)")
    elif by_nnz:
        rowlen = np.zeros(nrows, np.int64)
        np.add.at(rowlen, rows, 1)
        ranges = part.weighted_nnz_partition(rowlen, weights, align=1)
    else:
        ranges = part.weighted_row_partition(nrows, weights, align=1)

    locals_: List[SellCS] = []
    remotes: List[SellCS] = []
    rcols_all: List[np.ndarray] = []
    for (s, e) in ranges:
        m = e - s
        sel = (rows >= s) & (rows < e)
        r_p, c_p, v_p = rows[sel] - s, cols[sel], vals[sel]
        is_local = (c_p >= s) & (c_p < e)
        # local square part: shard-level sigma sorting + permuted columns
        L = from_coo(r_p[is_local], c_p[is_local] - s, v_p[is_local],
                     (m, m), C=C, sigma=sigma, w_align=w_align,
                     store_dtype=store_dtype)
        # remote part: compressed halo columns, same row perm as local
        rg = c_p[~is_local]
        rcols = np.unique(rg)                          # sorted ascending
        h = len(rcols)
        hidx = np.searchsorted(rcols, rg)
        R = from_coo(r_p[~is_local], hidx, v_p[~is_local],
                     (m, max(h, 1)), C=C, sigma=1, w_align=w_align,
                     store_dtype=store_dtype,
                     row_perm=np.asarray(L.perm, np.int64),
                     permute_columns=False)
        locals_.append(L)
        remotes.append(R)
        rcols_all.append(rcols)

    m_pad = max(L.nrows_pad for L in locals_)
    ncks = m_pad // C
    capL = max(L.cap for L in locals_)
    capR = max(R.cap for R in remotes)

    # ---- halo exchange maps ------------------------------------------------
    starts = np.array([s for (s, _) in ranges], np.int64)
    ends = np.array([e for (_, e) in ranges], np.int64)
    owner_of = np.zeros(nrows, np.int64)
    for q, (s, e) in enumerate(ranges):
        owner_of[s:e] = q
    send_lists = [[np.zeros(0, np.int64) for _ in range(nshards)]
                  for _ in range(nshards)]            # [src][dst]
    halo_entries = []                                  # per shard: (owner, rank)
    cnt = np.zeros((nshards, nshards), np.int64)       # cnt[src][dst]
    for p in range(nshards):
        rcols = rcols_all[p]
        owners = owner_of[rcols] if len(rcols) else np.zeros(0, np.int64)
        ent = np.zeros((len(rcols), 2), np.int64)
        for q in range(nshards):
            sel = owners == q
            g = rcols[sel]
            # owner-local (permuted) positions, ascending in g
            ipq = np.asarray(locals_[q].iperm, np.int64)
            send_lists[q][p] = ipq[g - starts[q]]
            ent[sel, 0] = q
            ent[sel, 1] = np.arange(sel.sum())
            cnt[q, p] = sel.sum()
        halo_entries.append(ent)
    max_msg = max(1, int(cnt.max()))
    h_max = max(1, max(len(r) for r in rcols_all))

    send_idx = np.zeros((nshards, nshards, max_msg), np.int64)
    for q in range(nshards):
        for p in range(nshards):
            sl = send_lists[q][p]
            send_idx[q, p, : len(sl)] = sl
    halo_idx = np.zeros((nshards, h_max), np.int64)
    for p in range(nshards):
        ent = halo_entries[p]
        halo_idx[p, : len(ent)] = ent[:, 0] * max_msg + ent[:, 1]

    # ---- vector maps --------------------------------------------------------
    g2l = np.full((nshards, m_pad), -1, np.int64)
    pos_of_global = np.zeros(nrows, np.int64)
    for p, (s, e) in enumerate(ranges):
        m = e - s
        permp = np.asarray(locals_[p].perm, np.int64)
        # local permuted slot j holds original row s + permp[j] (if < m)
        valid = permp < m
        g2l[p, : len(permp)][valid] = s + permp[valid]
        slots = np.nonzero(valid)[0]
        pos_of_global[s + permp[valid]] = p * m_pad + slots

    def stack(arrs, cap, pad_val=0, dt=None):
        out = np.full((nshards, cap), pad_val,
                      dt if dt is not None else np.asarray(arrs[0]).dtype)
        for i, a in enumerate(arrs):
            a = np.asarray(a)
            out[i, : a.shape[0]] = a
        return out

    # chunk arrays padded with zero-length chunks at offset cap//C
    def stack_chunks(mats, cap):
        offs = np.zeros((nshards, ncks), np.int64)
        lens = np.zeros((nshards, ncks), np.int64)
        for i, M in enumerate(mats):
            o = np.asarray(M.chunk_off)
            l = np.asarray(M.chunk_len)
            offs[i, : len(o)] = o
            lens[i, : len(l)] = l
            # padding chunks: zero length, offset clamped inside cap
            offs[i, len(o):] = 0
        return offs, lens

    l_off, l_len = stack_chunks(locals_, capL)
    r_off, r_len = stack_chunks(remotes, capR)

    def stacked_width(mats, cap):
        # a shard with fewer chunks maps its padding chunks onto the
        # zero padding of the stack, so only the widths must agree
        w = _uniform_width(np.concatenate(
            [np.asarray(M.chunk_len) for M in mats]))
        return w if cap == ncks * w * C else 0

    if mesh is None:
        put = jnp.asarray
    else:
        by_shard = NamedSharding(mesh, P(axis))
        put = partial(jax.device_put, device=by_shard)

    def idx(a):
        return put(np.asarray(a, np.int32))

    vdt = locals_[0].vals.dtype
    return DistSellCS(
        l_vals=put(stack([M.vals for M in locals_], capL, dt=vdt)),
        l_cols=idx(stack([M.cols for M in locals_], capL, dt=np.int64)),
        l_off=idx(l_off),
        l_len=idx(l_len),
        l_rowids=idx(stack([M.rowids for M in locals_], capL, dt=np.int64)),
        r_vals=put(stack([M.vals for M in remotes], capR, dt=vdt)),
        r_cols=idx(stack([M.cols for M in remotes], capR, dt=np.int64)),
        r_off=idx(r_off),
        r_len=idx(r_len),
        r_rowids=idx(stack([M.rowids for M in remotes], capR, dt=np.int64)),
        send_idx=idx(send_idx),
        halo_idx=idx(halo_idx),
        g2l=idx(g2l),
        pos_of_global=jnp.asarray(pos_of_global, jnp.int32),
        row_ranges=tuple((int(s), int(e)) for (s, e) in ranges),
        shard_nnz=tuple(int(L.nnz + R.nnz)
                        for L, R in zip(locals_, remotes)),
        nshards=nshards,
        C=C,
        sigma=sigma,
        w_align=w_align,
        nrows=nrows,
        m_pad=m_pad,
        max_msg=max_msg,
        h_max=h_max,
        compute_dtype=locals_[0].compute_dtype,
        l_width=stacked_width(locals_, capL),
        r_width=stacked_width(remotes, capR),
    )


# ---------------------------------------------------------------------------
# SPMD compute (runs inside shard_map; one shard's slice per device)
#
# The shard step is decomposed into named *stages* mirroring GHOST's
# task-mode SpMV (paper Fig. 5): pack -> exchange/unpack -> local -> remote
# -> epilogue.  ``dist_spmv_shard`` composes them for the classic one-shot
# path; ``repro.runtime.pipeline`` re-composes the same stages with
# double-buffered halo staging for the heterogeneous engine.
# ---------------------------------------------------------------------------

def _shard_spmv_ref(vals, cols, rowids, x, m_pad, acc_dt, C, width):
    contrib = vals[:, None].astype(acc_dt) * x[cols].astype(acc_dt)
    return row_sums(contrib, rowids, m_pad, C, width)


def _shard_spmv_pallas(vals, cols, off, ln, x, C, w_tile, interpret,
                       compute_dtype=None):
    from repro.kernels.sellcs_spmv import sellcs_spmv_pallas
    y, _, _ = sellcs_spmv_pallas(vals, cols, off, ln, x, C=C, w_tile=w_tile,
                                 compute_dtype=compute_dtype,
                                 interpret=interpret)
    return y


def halo_pack(shard: dict, x_local: jax.Array) -> jax.Array:
    """Stage 1: gather the owned rows each peer needs -> (P, max_msg, b)."""
    return x_local[shard["send_idx"]]


def halo_exchange_unpack(A: DistSellCS, shard: dict, sendbuf: jax.Array,
                         axis: str) -> jax.Array:
    """Stage 2: all_to_all the send buffer and compress the receive buffer
    into this shard's dense halo (remote-column compression, Fig. 3)."""
    b = sendbuf.shape[-1]
    recv = lax.all_to_all(sendbuf, axis, 0, 0, tiled=False)
    if recv.ndim == 4:                                  # (P,1,msg,b) squeeze
        recv = recv.reshape(A.nshards, A.max_msg, b)
    return recv.reshape(A.nshards * A.max_msg, b)[shard["halo_idx"]]


def local_stage(A: DistSellCS, shard: dict, x_local: jax.Array,
                *, impl: str, interpret: bool, acc_dt) -> jax.Array:
    """Stage 3: SpMV of the local (square) part — no communication.

    The value shard streams at its *storage* dtype; accumulation happens
    in ``acc_dt`` (the compute dtype joined with the vector dtype).
    """
    if impl == "pallas":
        return _shard_spmv_pallas(shard["l_vals"], shard["l_cols"],
                                  shard["l_off"], shard["l_len"], x_local,
                                  A.C, A.w_align, interpret,
                                  compute_dtype=acc_dt).astype(acc_dt)
    return _shard_spmv_ref(shard["l_vals"], shard["l_cols"],
                           shard["l_rowids"], x_local, A.m_pad, acc_dt,
                           A.C, A.l_width)


def remote_stage(A: DistSellCS, shard: dict, halo: jax.Array,
                 *, impl: str, interpret: bool, acc_dt) -> jax.Array:
    """Stage 4: SpMV of the remote part against the compressed halo."""
    if impl == "pallas":
        return _shard_spmv_pallas(shard["r_vals"], shard["r_cols"],
                                  shard["r_off"], shard["r_len"], halo,
                                  A.C, A.w_align, interpret,
                                  compute_dtype=acc_dt).astype(acc_dt)
    return _shard_spmv_ref(shard["r_vals"], shard["r_cols"],
                           shard["r_rowids"], halo, A.m_pad, acc_dt,
                           A.C, A.r_width)


def fused_epilogue(Ax: jax.Array, x_local: jax.Array, axis: str,
                   opts: SpmvOpts, acc_dt,
                   y_local: Optional[jax.Array] = None):
    """Stage 5: shift/scale/axpby + the fused dot products (psum'ed)."""
    b = x_local.shape[1]
    if opts.gamma is not None:
        Ax = Ax - jnp.asarray(opts.gamma, acc_dt) * x_local.astype(acc_dt)
    y = opts.alpha * Ax
    if y_local is not None:
        y = y + opts.beta * y_local.astype(acc_dt)

    dots = None
    if opts.any_dot:
        zero = jnp.zeros((b,), acc_dt)
        xl = x_local.astype(acc_dt)
        d = jnp.stack([
            jnp.sum(y * y, axis=0) if opts.dot_yy else zero,
            jnp.sum(xl * y, axis=0) if opts.dot_xy else zero,
            jnp.sum(xl * xl, axis=0) if opts.dot_xx else zero,
        ])
        dots = lax.psum(d, axis)
    return y, dots


def spmv_shard_stages(
    A: DistSellCS,
    shard: dict,
    x_local: jax.Array,            # (m_pad, b) shard-permuted
    axis: str,
    *,
    overlap: bool = True,
    impl: str = "ref",
    interpret: Optional[bool] = None,
    opts: SpmvOpts = SpmvOpts(),
    y_local: Optional[jax.Array] = None,
    staging: Optional[jax.Array] = None,   # (2, P, max_msg, b) double buffer
):
    """The full stage composition for one shard.  Returns (y, dots, staging').

    With ``staging`` the send buffer rotates through a two-slot array:
    slot 0 <- this call's packed rows, slot 1 <- the previous call's
    buffer (kept live until its exchange must have completed) — the
    double-buffered halo staging of the runtime pipeline.
    ``interpret=None`` defers to :mod:`repro.core.execution` (resolved at
    trace time).  A compiled-Pallas request on a backend that cannot
    lower it degrades to the ref stages with a one-time warning — the
    trace-time leg of the hardened cascade (a lowering error inside
    ``shard_map`` could not be caught later).
    """
    interpret = execution.resolve_interpret(interpret)
    if (impl == "pallas" and not interpret
            and execution.degrade_to_reference("dist_spmv[pallas]")):
        impl = "ref"
    # accumulate in the matrix' compute dtype (== value-shard dtype for
    # single-dtype matrices; wider when store_dtype narrows the shards)
    acc_dt = jnp.result_type(A.dtype, x_local.dtype)

    # --- stage 1: pack -----------------------------------------------------
    send = halo_pack(shard, x_local)
    if staging is not None:
        # rotate in the send buffer's own dtype: the retained slot 1 is
        # never computed on, so staging can never round the live halo
        # values (bit-identity with the unstaged schedule holds for any
        # initial staging dtype)
        staging = jnp.stack([send, staging[0].astype(send.dtype)])
        send = staging[0]

    # --- stage 2: halo exchange (independent of local compute) -------------
    halo = halo_exchange_unpack(A, shard, send, axis)

    # --- stage 3: local part (overlappable with the exchange) --------------
    if overlap:
        y_loc = local_stage(A, shard, x_local, impl=impl,
                            interpret=interpret, acc_dt=acc_dt)
    else:
        # paper Fig. 5 "No Overlap": force the exchange before local compute
        x_seq, halo = lax.optimization_barrier((x_local, halo))
        y_loc = local_stage(A, shard, x_seq, impl=impl,
                            interpret=interpret, acc_dt=acc_dt)

    # --- stage 4: remote part ----------------------------------------------
    y_rem = remote_stage(A, shard, halo, impl=impl, interpret=interpret,
                         acc_dt=acc_dt)

    # --- stage 5: fused epilogue -------------------------------------------
    y, dots = fused_epilogue(y_loc + y_rem, x_local, axis, opts, acc_dt,
                             y_local=y_local)
    return y, dots, staging


def dist_spmv_shard(
    A: DistSellCS,
    shard: dict,
    x_local: jax.Array,            # (m_pad, b) shard-permuted
    axis: str,
    *,
    overlap: bool = True,
    impl: str = "ref",
    interpret: Optional[bool] = None,
    opts: SpmvOpts = SpmvOpts(),
    y_local: Optional[jax.Array] = None,
):
    """One shard's fused distributed SpMV step (call inside shard_map).

    ``shard`` holds this shard's slices of the stacked arrays.  Returns
    (y_local, dots) with dots already psum'ed over ``axis``.
    """
    y, dots, _ = spmv_shard_stages(A, shard, x_local, axis, overlap=overlap,
                                   impl=impl, interpret=interpret, opts=opts,
                                   y_local=y_local)
    return y, dots


def _shard_view(A: DistSellCS) -> dict:
    """Names of the stacked arrays to pass through shard_map."""
    return dict(
        l_vals=A.l_vals, l_cols=A.l_cols, l_off=A.l_off, l_len=A.l_len,
        l_rowids=A.l_rowids,
        r_vals=A.r_vals, r_cols=A.r_cols, r_off=A.r_off, r_len=A.r_len,
        r_rowids=A.r_rowids,
        send_idx=A.send_idx, halo_idx=A.halo_idx,
    )


def make_dist_spmv(
    A: DistSellCS,
    mesh: Mesh,
    axis: str = "data",
    *,
    overlap: bool = True,
    impl: str = "ref",
    interpret: Optional[bool] = None,
    opts: SpmvOpts = SpmvOpts(),
    nvecs: int = 1,
) -> Callable[[jax.Array], Tuple[jax.Array, Optional[jax.Array]]]:
    """Build a jitted distributed SpMV over stacked shard-local vectors.

    The returned fn maps ``x_stacked (P, m_pad, nvecs)`` (see
    :meth:`DistSellCS.distribute_vec`) to ``(y_stacked, dots)``.
    ``interpret=None`` resolves through the central execution policy once
    at build time.
    """
    interpret = execution.resolve_interpret(interpret)
    sh = _shard_view(A)
    pspec = {k: P(axis, *([None] * (v.ndim - 1))) for k, v in sh.items()}

    def fn(shard, x):
        shard = {k: v[0] for k, v in shard.items()}
        y, dots = dist_spmv_shard(A, shard, x[0], axis, overlap=overlap,
                                  impl=impl, interpret=interpret, opts=opts)
        return y[None], (jnp.zeros((1, 3, nvecs), y.dtype) if dots is None
                         else dots[None].astype(y.dtype))

    # check_vma is off because pallas_call runs inside the shard_map
    mapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(pspec, P(axis, None, None)),
        out_specs=(P(axis, None, None), P(axis, None, None)),
        check_vma=False,
    )

    @jax.jit
    def run(x_stacked):
        y, dots = mapped(sh, x_stacked)
        return y, dots[0]

    return run


def dist_spmv(
    A: DistSellCS,
    mesh: Mesh,
    x: jax.Array,
    axis: str = "data",
    **kw,
):
    """Convenience: global original-space x -> global y (test-friendly)."""
    x2 = x[:, None] if x.ndim == 1 else x
    xs = A.distribute_vec(x2)
    run = make_dist_spmv(A, mesh, axis, nvecs=x2.shape[1], **kw)
    ys, dots = run(xs)
    y = A.collect_vec(ys)
    if x.ndim == 1:
        y = y[:, 0]
    return y, dots
