"""The heterogeneous execution engine (GHOST sections 4.1 + 4.2).

``HeterogeneousEngine`` is the piece that *decides* and *schedules*: it
classifies the available devices (:class:`DevicePool`), derives
roofline-proportional split weights, builds the C-aligned
:class:`SplitPlan` and the distributed SELL-C-sigma matrix for it, and
exposes pipelined (task-mode-overlapped) matvecs that the solvers consume
through :class:`repro.solvers.operator.DistOperator` unchanged.

Rebalance loop: ``engine.rebalance(times)`` takes measured per-shard SpMV
times, performs one hill-climb step on the weights and redistributes the
matrix.  With no measurements it falls back to the pool's roofline model,
making the call idempotent on a perfectly modeled pool (a property the
tests pin down).

Typical use::

    eng = HeterogeneousEngine.from_coo(r, c, v, n, mesh=mesh, C=32)
    y, dots = eng.spmv(x, opts=SpmvOpts(dot_xy=True))     # global space
    res = cg(eng.operator(), b_op)                        # solver, unchanged
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import execution
from repro.core.distributed import DistSellCS, dist_from_coo
from repro.core.spmv import SpmvOpts, as2d, pack_coefs
from repro.launch.costmodel import spmv_cost
from repro.runtime.devicepool import DevicePool
from repro.runtime.pipeline import init_staging, make_pipeline_spmv
from repro.runtime.split import SplitPlan, plan_split

__all__ = ["HeterogeneousEngine"]


class HeterogeneousEngine:
    """Cost-model-driven work splitting + overlapped halo pipeline."""

    def __init__(self, rows, cols, vals, nrows: int, *,
                 mesh=None, axis: str = "data",
                 pool: Optional[DevicePool] = None,
                 weights: Optional[Sequence[float]] = None,
                 nshards: Optional[int] = None,
                 C: int = 32, sigma: int = 1, w_align: int = 1,
                 by_nnz: bool = True, dtype=None, store_dtype=None):
        self._rows = np.asarray(rows, np.int64)
        self._cols = np.asarray(cols, np.int64)
        self._vals = np.asarray(vals) if dtype is None else \
            np.asarray(vals).astype(dtype)
        self.nrows = int(nrows)
        self.C, self.sigma, self.w_align = C, sigma, w_align
        # matrix values shard-stored narrower than the compute dtype
        # (None = single-dtype); vectors/halo stay in the compute dtype
        self.store_dtype = None if store_dtype is None \
            else jnp.dtype(store_dtype)
        self.axis = axis

        self.pool = pool if pool is not None else DevicePool.detect()
        if mesh is None:
            ndev = nshards or self.pool.ndevices
            devs = np.array(jax.devices()[:ndev])
            mesh = jax.sharding.Mesh(devs, (axis,))
        self.mesh = mesh
        self.nshards = (int(nshards) if nshards
                        else int(np.prod(mesh.devices.shape)))
        mesh_size = int(np.prod(mesh.devices.shape))
        if self.nshards != mesh_size:
            raise ValueError(
                f"nshards={self.nshards} must equal the mesh size "
                f"({mesh_size} devices); pass a matching mesh or run in a "
                f"process with enough devices "
                f"(XLA_FLAGS=--xla_force_host_platform_device_count=N)")

        vb = self._val_bytes()
        if weights is None:
            w = self.pool.device_weights(nnz=len(self._vals),
                                         nrows=self.nrows, val_bytes=vb)
            # pool size and shard count may differ (e.g. tests); tile/trim
            w = np.resize(w, self.nshards)
        else:
            w = np.asarray(weights, np.float64)
            if len(w) != self.nshards:
                raise ValueError(f"expected {self.nshards} shard weights, "
                                 f"got {len(w)}")
        rowlen = None
        if by_nnz:
            rowlen = np.zeros(self.nrows, np.int64)
            np.add.at(rowlen, self._rows, 1)
        self.plan: SplitPlan = plan_split(self.nrows, w, align=C,
                                          rowlen=rowlen)
        self._build()

    # ------------------------------------------------------------ plumbing
    @classmethod
    def from_coo(cls, rows, cols, vals, nrows, **kw) -> "HeterogeneousEngine":
        return cls(rows, cols, vals, nrows, **kw)

    def _val_bytes(self) -> int:
        """Bytes per stored matrix value — the roofline traffic number.

        Uses the *storage* dtype: a bf16-stored matrix moves half the
        value bytes of its f32 compute dtype, and the cost-model split
        weights must see that.
        """
        if self.store_dtype is not None:
            return int(jnp.dtype(self.store_dtype).itemsize)
        return int(self._vals.dtype.itemsize)

    def _build(self) -> None:
        self.A: DistSellCS = dist_from_coo(
            self._rows, self._cols, self._vals, self.nrows,
            nshards=self.plan.nshards, C=self.C, sigma=self.sigma,
            w_align=self.w_align, store_dtype=self.store_dtype,
            ranges=self.plan.ranges, mesh=self.mesh, axis=self.axis)
        self._matvec_cache: Dict[tuple, object] = {}

    def make_matvec(self, *, overlap: bool = True, impl: str = "ref",
                    interpret: Optional[bool] = None, nvecs: int = 1,
                    with_y: bool = False, dot_yy: bool = False,
                    dot_xy: bool = False, dot_xx: bool = False,
                    has_gamma: bool = False, double_buffer: bool = False):
        """Cached, jitted pipelined matvec (see make_pipeline_spmv).

        ``interpret=None`` resolves through the central execution policy
        *here*, before the cache key, so an ``execution.force`` scope (or
        the backend auto-detection) picks the right compiled variant and
        distinct modes never share a trace.  The policy's ``fallback``
        flag is part of the key too: it changes the traced program (the
        shard stages' degrade-to-reference decision), so a
        ``force(fallback=False)`` scope must not reuse a degraded trace.
        The value-shard storage dtype and the compute dtype join the key
        for the same reason: they change the traced program (in-register
        upcast vs native accumulate) and must never share a trace.
        """
        interpret = execution.resolve_interpret(interpret)
        key = (overlap, impl, interpret,
               execution.current_policy().fallback, nvecs, with_y,
               dot_yy, dot_xy, dot_xx, has_gamma, double_buffer,
               str(self.A.store_dtype), str(self.A.dtype))
        fn = self._matvec_cache.get(key)
        if fn is None:
            fn = make_pipeline_spmv(
                self.A, self.mesh, self.axis, overlap=overlap, impl=impl,
                interpret=interpret, nvecs=nvecs, with_y=with_y,
                dot_yy=dot_yy, dot_xy=dot_xy, dot_xx=dot_xx,
                has_gamma=has_gamma, double_buffer=double_buffer)
            self._matvec_cache[key] = fn
        return fn

    def init_staging(self, nvecs: int = 1, dtype=None) -> jax.Array:
        # staging holds *vector* (halo) data: compute dtype, never the
        # narrower matrix storage dtype
        return init_staging(self.A, nvecs, dtype or self.A.dtype)

    # ------------------------------------------------------------- spmv API
    def spmv(self, x: jax.Array, y: Optional[jax.Array] = None, *,
             opts: SpmvOpts = SpmvOpts(), overlap: bool = True,
             impl: str = "ref", interpret: Optional[bool] = None
             ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """Global original-space fused SpM(M)V through the pipeline.

        Convenience mirror of ``core.distributed.dist_spmv`` running on the
        engine's split + overlap schedule.  Returns (y, dots).
        """
        x2, was1d = as2d(x)
        nvecs = x2.shape[1]
        xs = self.A.distribute_vec(x2)
        ys = None
        if y is not None:
            ys = self.A.distribute_vec(as2d(y)[0])
        run = self.make_matvec(overlap=overlap, impl=impl,
                               interpret=interpret, nvecs=nvecs,
                               with_y=y is not None,
                               dot_yy=opts.dot_yy, dot_xy=opts.dot_xy,
                               dot_xx=opts.dot_xx,
                               has_gamma=opts.gamma is not None)
        coefs = pack_coefs(opts, nvecs, self.A.dtype)
        ys_out, dots, _ = run(xs, ys, coefs)
        out = self.A.collect_vec(ys_out)
        if was1d:
            out = out[:, 0]
        return out, dots

    def operator(self, **kw):
        """Solver-facing distributed operator (CG/Lanczos/KPM unchanged)."""
        from repro.solvers.operator import DistOperator
        return DistOperator(self, **kw)

    # ------------------------------------------------------- rebalance loop
    def modeled_shard_times(self, nvecs: int = 1) -> np.ndarray:
        """Roofline time of each shard's SpMV on its assigned device."""
        classes = self.pool.device_classes()
        vb = self._val_bytes()
        times = []
        for i, (s, e) in enumerate(self.plan.ranges):
            cost = spmv_cost(int(self.A.shard_nnz[i]), max(e - s, 1),
                             val_bytes=vb, nvecs=nvecs)
            times.append(classes[i % len(classes)].time_for(cost))
        return np.asarray(times)

    def modeled_iter_seconds(self, nvecs: int = 1) -> float:
        """Roofline estimate of one block-SpMV sweep: the slowest shard.

        The halo pipeline overlaps remote staging with local compute, so
        one distributed matvec takes (about) the critical-path shard
        time.  One Krylov iteration is one sweep plus vector work the
        sweep dominates, which makes this a serviceable *cold-start*
        seconds-per-iteration hint for deadline scheduling — the serving
        frontend replaces it with measured chunk times as soon as it has
        any (see ``SolverService._run_chunk``).
        """
        return float(np.max(self.modeled_shard_times(nvecs=nvecs)))

    def rebalance(self, measured_times: Optional[Sequence[float]] = None, *,
                  step: float = 0.5) -> "HeterogeneousEngine":
        """One hill-climb step on the split weights; redistributes A.

        ``measured_times[i]`` = observed SpMV seconds of shard ``i`` under
        the current plan (e.g. timed around ``make_matvec`` calls, or from
        a profiler).  Falls back to :meth:`modeled_shard_times`.  Returns
        ``self`` (mutated) for chaining.
        """
        t = (np.asarray(measured_times, np.float64)
             if measured_times is not None else self.modeled_shard_times())
        new_plan = self.plan.rebalance(t, step=step)
        if new_plan.ranges == self.plan.ranges:
            # at the fixed point (block granularity absorbed the weight
            # nudge): keep the matrix and the compiled matvecs
            self.plan = new_plan
            return self
        self.plan = new_plan
        self._build()
        return self

    def __repr__(self) -> str:
        shares = "/".join(f"{w:.3f}" for w in self.plan.weights)
        return (f"HeterogeneousEngine(n={self.nrows}, shards={self.nshards}, "
                f"gen={self.plan.generation}, weights={shares}, "
                f"pool={self.pool!r})")
