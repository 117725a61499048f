"""Overlapped halo pipeline with double-buffered staging (GHOST 4.2, Fig. 5).

GHOST hides the halo exchange behind the local SpMV by putting the
communication in a *task* that runs concurrently with the local compute
kernel.  The XLA mapping of that idea is data-flow independence: the local
stage consumes only ``x_local`` while the ``all_to_all`` runs, so the async
collective scheduler may overlap them; ``overlap=False`` reinstates the
paper's "No Overlap" baseline with an optimization barrier.

What this module adds over ``core.distributed.dist_spmv_shard``:

* the shard step is recomposed from the *named stages* exported by
  ``core.distributed`` (pack / exchange+unpack / local / remote /
  epilogue) so schedules can be rearranged without touching the math;
* **double-buffered halo staging**: each call packs its send buffer into
  slot 0 of a two-slot staging array while slot 1 keeps the previous
  call's buffer alive.  Across a chained sequence of SpMVs (CG sweeps,
  KPM recurrences) iteration ``k+1``'s pack therefore never write-after-
  read depends on iteration ``k``'s possibly in-flight exchange — the
  invariant GHOST's MPI task-mode needs two buffers for.  Under XLA's
  SSA semantics that invariant already holds implicitly, so today the
  staging array is *structural*: it materializes the buffer rotation as
  a carried value (a measurable copy per call — fig5 reports it as
  ``staging_overhead``) and is the hook where a future Pallas RDMA
  exchange would pin its landing buffers, which is when the two slots
  become load-bearing;
* traced coefficients: alpha/beta/gamma arrive as a ``(3, b)`` operand so
  solvers can change them every iteration without retracing;
* dtype contract: the halo/staging buffers carry *vector* data and stay
  in the compute dtype; the matrix value shards (``l_vals``/``r_vals``)
  stay in their **storage** dtype end-to-end — a mixed-precision matrix
  streams narrow values through both the local and the remote stage and
  upcasts in-register only (``docs/mixed_precision.md``).

All functions here run *inside* ``shard_map`` except
:func:`make_pipeline_spmv`, which builds the jitted SPMD callable.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import execution
from repro.core.distributed import (
    DistSellCS, _shard_view, spmv_shard_stages,
)
from repro.core.spmv import SpmvOpts

__all__ = ["make_pipeline_spmv", "init_staging"]


def init_staging(A: DistSellCS, nvecs: int, dtype) -> jax.Array:
    """Fresh double-buffer halo staging: (nshards, 2, P, max_msg, nvecs)."""
    return jnp.zeros((A.nshards, 2, A.nshards, A.max_msg, nvecs),
                     jnp.dtype(dtype))


def make_pipeline_spmv(
    A: DistSellCS,
    mesh: Mesh,
    axis: str = "data",
    *,
    overlap: bool = True,
    impl: str = "ref",
    interpret: Optional[bool] = None,
    nvecs: int = 1,
    with_y: bool = False,
    dot_yy: bool = False,
    dot_xy: bool = False,
    dot_xx: bool = False,
    has_gamma: bool = False,
    double_buffer: bool = False,
):
    """Build the jitted SPMD pipelined SpMV over stacked shard vectors.

    Returns ``run(x_stacked, y_stacked=None, coefs=None, staging=None)``
    mapping ``(P, m_pad, nvecs)`` inputs to ``(y_stacked, dots, staging')``.
    ``coefs`` is a ``(3, nvecs)`` array of per-column (alpha, beta, gamma)
    — traced, so solvers vary them iteration-to-iteration for free.  The
    static flags (``with_y``, dot selection, ``has_gamma``) pick the
    specialized kernel, mirroring GHOST's compile-time codegen (C6).
    ``interpret=None`` resolves through the central execution policy once
    at build time — the returned callable is pinned to that mode.
    """
    interpret = execution.resolve_interpret(interpret)
    sh = _shard_view(A)
    pspec = {k: P(axis, *([None] * (v.ndim - 1))) for k, v in sh.items()}
    vec = P(axis, None, None)
    stg = P(axis, None, None, None, None)

    in_specs = [pspec, vec]
    if with_y:
        in_specs.append(vec)
    in_specs.append(P(None, None))                 # coefs, replicated
    if double_buffer:
        in_specs.append(stg)

    out_specs = (vec, vec) + ((stg,) if double_buffer else ())

    def fn(shard, x, *rest):
        shard = {k: v[0] for k, v in shard.items()}
        rest = list(rest)
        y_local = rest.pop(0)[0] if with_y else None
        coefs = rest.pop(0)
        staging = rest.pop(0)[0] if double_buffer else None
        opts = SpmvOpts(alpha=coefs[0], beta=coefs[1],
                        gamma=coefs[2] if has_gamma else None,
                        dot_yy=dot_yy, dot_xy=dot_xy, dot_xx=dot_xx)
        y, dots, staging = spmv_shard_stages(
            A, shard, x[0], axis, overlap=overlap, impl=impl,
            interpret=interpret, opts=opts, y_local=y_local, staging=staging)
        dots_out = (jnp.zeros((1, 3, nvecs), y.dtype) if dots is None
                    else dots[None].astype(y.dtype))
        out = (y[None], dots_out)
        if double_buffer:
            out = out + (staging[None],)
        return out

    # check_vma is off because pallas_call runs inside the shard_map
    mapped = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
        check_vma=False))
    any_dot = dot_yy or dot_xy or dot_xx

    def run(x_stacked, y_stacked=None, coefs=None, staging=None):
        if coefs is None:
            coefs = jnp.zeros((3, nvecs), x_stacked.dtype).at[0].set(1.0)
        args = [sh, x_stacked]
        if with_y:
            if y_stacked is None:
                raise ValueError(
                    "pipeline built with with_y=True needs y_stacked")
            args.append(y_stacked)
        args.append(coefs)
        if double_buffer:
            if staging is None:
                staging = init_staging(A, nvecs, x_stacked.dtype)
            args.append(staging)
        out = mapped(*args)
        y, dots = out[0], (out[1][0] if any_dot else None)
        return y, dots, (out[2] if double_buffer else None)

    return run
