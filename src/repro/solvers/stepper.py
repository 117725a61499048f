"""Shared machinery for resumable stepper-form Krylov solvers.

Each solver in this package factors into ``*_init(op, b, x0) -> State``,
``*_step(op, state, k) -> State`` and ``*_finalize(state) -> Result``.
A *State* is a NamedTuple of arrays whose per-column fields carry the
block-vector column as their **last** axis (``(n, b)`` vectors, ``(b,)``
recurrence scalars, ``(b,)`` bool ``done``) plus scalar bookkeeping
(``it``, ``maxiter``).  That layout is what makes continuous batching
possible: :func:`merge_columns` can splice freshly initialized columns
into a running state without touching the survivors.

``*_step`` runs a bounded ``lax.while_loop`` — up to ``k`` applications
of the *same* iteration body the monolithic solver uses, stopping early
at ``maxiter`` or when every column has converged.  Composing chunks is
therefore bit-identical to one monolithic solve: the body sees the same
carries in the same order, only the Python-level chunk boundaries move.

:func:`run_chunk` caches one jitted chunk per ``(operator, solver, k)``
(weakly keyed on the operator), so a long-lived
:class:`repro.runtime.service.SolverService` pays for tracing once per
batch shape, not once per request.
"""
from __future__ import annotations

import weakref
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.trace import scope

__all__ = ["run_chunk", "merge_columns", "merge_columns_masked",
           "snap_chunk", "clear_chunk_cache", "chunk_programs"]

# op -> {(solver_name, k): jitted chunk}; weak so dropping an operator
# (e.g. a registry eviction) frees its compiled chunks too
_chunk_cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def run_chunk(op, name: str, k: int, state, body: Callable, *,
              extra_key=None):
    """Advance ``state`` by up to ``k`` iterations of ``body(op, state)``.

    The loop stops early once ``state.it`` reaches ``state.maxiter`` or
    every column's ``done`` flag is set — exactly the monolithic solver's
    termination test, so chunking never changes the iterate sequence.

    ``extra_key`` distinguishes otherwise same-named chunks whose bodies
    close over different auxiliary objects (e.g. the preconditioner ``M``
    of ``cg_step(..., M=M)``): two M's on the same operator must not
    share a compiled chunk.  It is held weakly in the cache key, so a
    dead preconditioner's entry can never collide with a new object.
    """
    k = int(k)
    if k <= 0:
        return state
    try:
        per_op = _chunk_cache[op]
    except KeyError:
        per_op = _chunk_cache[op] = {}
    cache_key = ((name, k) if extra_key is None
                 else (name, k, weakref.ref(extra_key)))
    fn = per_op.get(cache_key)
    if fn is None:
        # close over a weakref, not the operator: the cached jitted fn is
        # a *value* of the WeakKeyDictionary — a strong reference back to
        # its key would make the entry immortal.  The ref is live whenever
        # tracing happens (run_chunk holds ``op``), so resolution is safe.
        op_ref = weakref.ref(op)

        def chunk(st):
            o = op_ref()
            if o is None:
                raise ReferenceError(
                    "operator died while its chunk traced")

            def cond(carry):
                i, s = carry
                return jnp.logical_and(
                    i < k,
                    jnp.logical_and(s.it < s.maxiter, ~jnp.all(s.done)))

            def step(carry):
                i, s = carry
                return i + 1, body(o, s)

            with scope("chunk"):
                _, out = jax.lax.while_loop(cond, step,
                                            (jnp.asarray(0), st))
            return out

        fn = jax.jit(chunk)
        per_op[cache_key] = fn
    return fn(state)


def chunk_programs(op) -> dict:
    """The jitted chunks cached for ``op``, by ``(solver, k)`` (with a
    weak reference to ``extra_key`` third where one was given).  Each
    compiles (or loads from the compile cache) at its first call;
    lowered with a state, its compiled HLO text names each device
    operation's ``ghost.*`` scope in the ``op_name`` metadata."""
    return dict(_chunk_cache.get(op, {}))


def snap_chunk(k, k_max: int) -> int:
    """Clamp a desired chunk length to ``[1, k_max]``, snapped down to a
    power of two.

    :func:`run_chunk` compiles one program per ``(operator, solver, k)``,
    so a scheduler that derived ``k`` from a continuous quantity (time
    to a deadline / seconds per iteration) would compile an unbounded
    family of chunks.  Snapping to powers of two keeps the family at
    ``log2(k_max) + 1`` variants while staying within a factor of two of
    the requested length — good enough for deadline work, bounded enough
    for the jit cache.
    """
    k_max = int(k_max)
    if k_max < 1:
        raise ValueError(f"k_max must be >= 1, got {k_max}")
    k = int(k)
    if k >= k_max:
        return k_max
    if k < 1:
        return 1
    return 1 << (k.bit_length() - 1)


def merge_columns_masked(old_state, fresh_state, mask):
    """:func:`merge_columns` with the selection as a ``(b,)`` bool array.

    Pure function of arrays — jit it once and every refill pattern reuses
    the same trace (the mask is data, not structure).

    Block-Krylov states (``BLOCK_COUPLED``) cannot be column-spliced:
    their carried ``(b, b)`` Gram/reflection blocks couple every column,
    so a per-column mask would stitch together inconsistent Krylov
    spaces.  The service refills those with a warm restart instead (see
    ``SolverService._refill_block``).
    """
    if getattr(old_state, "BLOCK_COUPLED", False):
        raise ValueError(
            f"{type(old_state).__name__} carries cross-column (b, b) "
            f"blocks and cannot be column-spliced; refill block-Krylov "
            f"batches with a warm restart (re-init with carried x0)")

    def pick(old, fresh):
        if jnp.ndim(old) == 0:
            return old
        sel = mask if jnp.ndim(old) == 1 else mask[None, :]
        return jnp.where(sel, fresh, old)

    return type(old_state)(*(pick(o, f) for o, f in zip(old_state,
                                                        fresh_state)))


def merge_columns(old_state, fresh_state, cols):
    """Splice columns ``cols`` of ``fresh_state`` into ``old_state``.

    Per-column fields (last axis = block column) take the fresh values at
    ``cols`` and keep the running values elsewhere; scalar bookkeeping
    (``it``, ``maxiter``) always keeps the running values, so the block
    iteration counter keeps counting across refills.
    """
    width = old_state.done.shape[0]
    mask = np.zeros(width, bool)
    mask[list(cols)] = True
    return merge_columns_masked(old_state, fresh_state, jnp.asarray(mask))


def clear_chunk_cache() -> None:
    """Drop every cached jitted chunk (tests / backend resets)."""
    _chunk_cache.clear()
