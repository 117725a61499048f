"""Centralized kernel execution policy (paper section 5.4, C6).

GHOST's specialization cascade promises that the *same* call site runs the
fastest kernel the hardware supports and degrades gracefully otherwise.
This module is the single place where that decision is made for every
Pallas kernel in the repo:

* **Backend auto-detection** — compiled Pallas on TPU, interpret mode
  everywhere else (``jax.default_backend()``), so the high-performance
  path engages automatically on real hardware while CPU development and
  CI keep working unchanged.
* **Overrides** — the ``REPRO_INTERPRET`` env var (``0``/``1``/``auto``)
  pins the mode process-wide; :func:`force` pins it (and any tile knob)
  for a lexical scope::

      with execution.force(interpret=True):
          y, _, _ = ops.sellcs_spmv(A, x)      # interpreter, regardless

* **Tile knobs** — per-kernel tile sizes (``w_tile``, ``row_tile``,
  ``s_blk``) ride on the policy with env-var overrides and a small
  :func:`autotune` measure-and-cache hook.
* **Hardened cascade** — :func:`cascade` runs the specialized kernel and,
  if the *compiled* path fails (e.g. mode forced on a backend without
  Pallas support), falls back to the jnp reference with a one-time
  warning instead of crashing.  Interpret-mode failures still raise:
  those are logic bugs, not capability gaps.  On a TPU backend the
  fallback is off: a compiled failure there raises, so no measurement
  on the chip can silently time the reference.

Resolution happens at trace time.  A function jitted under one policy
keeps its compiled mode until retraced; enter :func:`force` *before*
tracing (or build separate jitted callables per mode, as
``runtime.engine.make_matvec`` does via its cache key).  Likewise
:func:`cascade` can only catch failures that surface while the wrapper
runs — eager calls and the wrapper's own trace; a failure inside an
enclosing ``jax.jit`` surfaces at that jit's compile time.

Env vars: ``REPRO_INTERPRET``, ``REPRO_W_TILE``, ``REPRO_ROW_TILE``,
``REPRO_S_BLK``, ``REPRO_FALLBACK``.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
import warnings
from contextlib import contextmanager
from typing import Any, Callable, Iterable, Optional, Sequence, TypeVar

import jax

__all__ = [
    "ExecutionPolicy", "default_policy", "current_policy", "force",
    "resolve_interpret", "resolve_w_tile", "resolve_row_tile",
    "resolve_s_blk", "cascade", "compiled_available",
    "degrade_to_reference", "autotune", "describe", "reset",
    "use_compile_cache",
]

T = TypeVar("T")

ENV_INTERPRET = "REPRO_INTERPRET"
ENV_W_TILE = "REPRO_W_TILE"
ENV_ROW_TILE = "REPRO_ROW_TILE"
ENV_S_BLK = "REPRO_S_BLK"
ENV_FALLBACK = "REPRO_FALLBACK"

#: backends whose Pallas lowering we trust enough to compile by default
COMPILED_BACKENDS = ("tpu",)

_TRUE = frozenset({"1", "true", "yes", "on"})
_FALSE = frozenset({"0", "false", "no", "off"})


@dataclasses.dataclass(frozen=True)
class ExecutionPolicy:
    """One resolved answer to "how should a kernel run right now?".

    ``interpret`` is the load-bearing bit; ``source`` records who decided
    (``auto`` backend detection, ``env`` override, or a ``forced``
    context) so benchmarks can report what actually ran.  The tile knobs
    are defaults only — an explicit keyword at a call site always wins.
    """

    interpret: bool
    backend: str
    source: str = "auto"                  # "auto" | "env" | "forced"
    w_tile: Optional[int] = None          # None -> per-matrix w_align
    row_tile: int = 512
    s_blk: int = 64
    fallback: bool = True                 # cascade to jnp ref on failure
                                          # (default off on a compiled backend)

    @property
    def mode(self) -> str:
        return "interpret" if self.interpret else "compiled"


def _env_bool(name: str) -> Optional[bool]:
    raw = os.environ.get(name, "").strip().lower()
    if raw in _TRUE:
        return True
    if raw in _FALSE:
        return False
    return None                            # unset / "auto" / unparsable


def _env_int(name: str) -> Optional[int]:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        v = int(raw)
    except ValueError:
        warnings.warn(f"ignoring non-integer {name}={raw!r}", RuntimeWarning)
        return None
    return v if v > 0 else None


class _Stack(threading.local):
    def __init__(self):
        self.policies: list = []


_stack = _Stack()
_default: Optional[ExecutionPolicy] = None
_warned: set = set()
_tune_cache: dict = {}
_compiled_ok: Optional[bool] = None


def default_policy() -> ExecutionPolicy:
    """The process-level policy: env overrides over backend detection.

    Cached after the first call (which initializes the JAX backend);
    :func:`reset` invalidates the cache, e.g. after monkeypatching env
    vars in tests.
    """
    global _default
    if _default is None:
        backend = jax.default_backend()
        env = _env_bool(ENV_INTERPRET)
        if env is None:
            interpret, source = backend not in COMPILED_BACKENDS, "auto"
        else:
            interpret, source = env, "env"
        _default = ExecutionPolicy(
            interpret=interpret,
            backend=backend,
            source=source,
            w_tile=_env_int(ENV_W_TILE),
            row_tile=_env_int(ENV_ROW_TILE) or 512,
            s_blk=_env_int(ENV_S_BLK) or 64,
            fallback=(backend not in COMPILED_BACKENDS
                      and _env_bool(ENV_FALLBACK) is not False),
        )
    return _default


def current_policy() -> ExecutionPolicy:
    """The active policy: innermost :func:`force` scope, else the default."""
    if _stack.policies:
        return _stack.policies[-1]
    return default_policy()


@contextmanager
def force(interpret: Optional[bool] = None, *,
          w_tile: Optional[int] = None,
          row_tile: Optional[int] = None,
          s_blk: Optional[int] = None,
          fallback: Optional[bool] = None):
    """Pin policy fields for a lexical scope (thread-local, re-entrant)."""
    repl: dict = {"source": "forced"}
    for k, v in (("interpret", interpret), ("w_tile", w_tile),
                 ("row_tile", row_tile), ("s_blk", s_blk),
                 ("fallback", fallback)):
        if v is not None:
            repl[k] = v
    pol = dataclasses.replace(current_policy(), **repl)
    _stack.policies.append(pol)
    try:
        yield pol
    finally:
        _stack.policies.pop()


# ------------------------------------------------------------------ resolvers
def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Explicit call-site argument wins; ``None`` defers to the policy."""
    return current_policy().interpret if interpret is None else bool(interpret)


def resolve_w_tile(w_tile: Optional[int], w_align: int) -> int:
    """Call-site arg > policy knob (when compatible) > matrix w_align.

    A policy-sourced width that doesn't divide into the matrix alignment
    degrades to ``w_align`` rather than raising: the knob is a hint, the
    call-site argument a contract.
    """
    if w_tile is not None:
        return int(w_tile)
    pw = current_policy().w_tile
    if pw is not None and (w_align % pw == 0 or pw % w_align == 0):
        return int(pw)
    return int(w_align)


def resolve_row_tile(row_tile: Optional[int] = None) -> int:
    return int(current_policy().row_tile if row_tile is None else row_tile)


def resolve_s_blk(s_blk: Optional[int] = None) -> int:
    return int(current_policy().s_blk if s_blk is None else s_blk)


# ------------------------------------------------------------------- cascade
def compiled_available() -> bool:
    """Whether this backend can lower + run a compiled Pallas kernel.

    Probed once per process with a trivial eager ``pallas_call`` (result
    cached; :func:`reset` clears it).  The probe makes the cascade a
    Python-level branch at *trace* time, so a forced-compiled policy on a
    Pallas-less backend falls back cleanly even inside ``lax.while_loop``
    solver bodies, where a lowering error could not be caught.  On a
    backend that should compile Pallas (``COMPILED_BACKENDS``) a failed
    probe raises instead of answering False.
    """
    global _compiled_ok
    if _compiled_ok is None:
        try:
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            def _probe(x_ref, o_ref):
                o_ref[...] = x_ref[...] + 1.0

            # the probe *implements* the policy the cascade rule guards,
            # and must pin compiled mode to test it
            # ghostlint: disable=GL001
            call = pl.pallas_call(
                _probe,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                interpret=False,  # ghostlint: disable=GL002
            )
            # AOT lower+compile: never binds into an ambient trace, so
            # the probe is safe (and meaningful) even when first hit
            # while tracing a shard_map/jit body — an eager call there
            # would be staged out and "succeed" unexecuted.
            jax.jit(call).lower(
                jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile()
            _compiled_ok = True
        # any lowering/compile failure means "compiled unavailable" —
        # the probe's whole job is to swallow it, except where the
        # backend should compile Pallas
        # ghostlint: disable=GL008
        except Exception as e:                              # noqa: BLE001
            backend = default_policy().backend
            if backend in COMPILED_BACKENDS:
                raise RuntimeError(
                    f"compiled Pallas probe failed on backend {backend!r}: "
                    f"{type(e).__name__}: {e}") from e
            _compiled_ok = False
    return _compiled_ok


def _warn_once(kernel: str, msg: str) -> None:
    if kernel not in _warned:
        _warned.add(kernel)
        warnings.warn(msg, RuntimeWarning, stacklevel=4)


def degrade_to_reference(kernel: str) -> bool:
    """True if a compiled-path request must degrade to the reference.

    The branch-only face of :func:`cascade`, for call sites that pick an
    implementation *before* tracing (e.g. the distributed shard stages,
    where a Pallas lowering error inside ``shard_map``/``jit`` could not
    be caught).  Warns once per kernel when it returns True; honors
    ``fallback=False`` by returning False so the failure stays fatal.
    """
    pol = current_policy()
    if not pol.fallback or compiled_available():
        return False
    _warn_once(kernel, (
        f"{kernel}: compiled Pallas is unavailable on backend "
        f"{pol.backend!r}; falling back to the jnp reference "
        f"(warned once per kernel)"))
    return True


def cascade(kernel: str,
            specialized: Callable[[], T],
            reference: Optional[Callable[[], T]] = None,
            *,
            interpret: Optional[bool] = None) -> T:
    """Hardened specialization cascade (paper 5.4).

    Runs ``specialized()``.  If the policy resolved to the *compiled*
    path and the backend can't take it — mode forced on a backend
    without Pallas lowering (checked up front via
    :func:`compiled_available`, so it also works under tracing), or a
    residual failure while the specialized call runs — falls back to
    ``reference()`` with a one-time ``RuntimeWarning`` per kernel name.
    Interpret-mode failures always propagate (they are correctness bugs).
    Compiled failures are fatal on a TPU backend (the policy's fallback
    default is off there), and anywhere under ``REPRO_FALLBACK=0`` or
    ``force(fallback=False)``.
    """
    pol = current_policy()
    it = pol.interpret if interpret is None else bool(interpret)
    if it or not pol.fallback or reference is None:
        return specialized()
    if not compiled_available():
        _warn_once(kernel, (
            f"{kernel}: compiled Pallas is unavailable on backend "
            f"{pol.backend!r}; falling back to the jnp reference "
            f"(warned once per kernel)"))
        return reference()
    try:
        return specialized()
    # the hardening contract: a compiled-path failure of *any* kind
    # degrades to the reference instead of crashing the solve
    # ghostlint: disable=GL008
    except Exception as e:                                  # noqa: BLE001
        _warn_once(kernel, (
            f"{kernel}: compiled Pallas path failed on backend "
            f"{pol.backend!r} ({type(e).__name__}: {e}); falling back "
            f"to the jnp reference (warned once per kernel)"))
        return reference()


# ------------------------------------------------------------------ autotune
def autotune(kernel: str,
             key: Any,
             candidates: Sequence[T],
             run: Callable[[T], Any],
             *,
             dtype: Any = None,
             iters: int = 3) -> T:
    """Tiny measure-and-cache tile picker.

    Times ``run(c)`` (block_until_ready'd) for each candidate knob value
    and returns the fastest; the winner is cached per
    ``(kernel, key, dtype, backend, mode)`` for the life of the process.
    ``key`` should capture whatever shapes the decision (e.g.
    ``(n, b)``); ``dtype`` is a dedicated key component for the operand
    dtype(s) — pass *both* the storage and the compute dtype for
    mixed-precision matrices (e.g. ``(A.store_dtype, A.dtype)``), since a
    narrower value stream shifts the bandwidth balance and therefore the
    optimal tile.  Call sites use this opportunistically::

        rt = execution.autotune("tsmttsm", (n, m, k), (256, 512, 1024),
                                lambda t: ops.tsmttsm(V, W, row_tile=t),
                                dtype=str(V.dtype))
    """
    pol = current_policy()
    ck = (kernel, key, None if dtype is None else str(dtype),
          pol.backend, pol.interpret)
    hit = _tune_cache.get(ck)
    if hit is not None:
        return hit
    best, best_t = None, float("inf")
    for cand in candidates:
        jax.block_until_ready(run(cand))                    # warm/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(run(cand))
        dt = (time.perf_counter() - t0) / iters
        if dt < best_t:
            best, best_t = cand, dt
    _tune_cache[ck] = best
    return best


# ------------------------------------------------------------------- plumbing
def describe(pol: Optional[ExecutionPolicy] = None) -> str:
    """One-line policy summary for benchmark output."""
    p = pol if pol is not None else current_policy()
    knobs = f"row_tile={p.row_tile};s_blk={p.s_blk}"
    if p.w_tile is not None:
        knobs += f";w_tile={p.w_tile}"
    return (f"mode={p.mode};backend={p.backend};source={p.source};"
            f"fallback={p.fallback};{knobs}")


def use_compile_cache(checkout: str) -> str:
    """Turn on JAX's persistent compile cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is honoured as JAX reads
    it; otherwise the cache sits at the fixed ``<checkout>/.jax_cache``
    (a fixed path, because the path is part of what a later run must
    find again).  Returns the directory in use.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(checkout), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def reset() -> None:
    """Drop every process-level cache (default policy, warnings, autotune).

    For tests that monkeypatch ``REPRO_*`` env vars mid-process.
    """
    global _default, _compiled_ok
    _default = None
    _compiled_ok = None
    _warned.clear()
    _tune_cache.clear()
