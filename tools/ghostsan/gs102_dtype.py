"""GS102 — jaxpr dtype-flow audit of the storage/accumulate contract.

The contract (``core/spmv.py::storage_acc_dtype`` / ``dot_acc_dtype``):
bf16/f16 are *storage* formats — narrow values widen exactly once on
the way into an accumulator, accumulators never silently drop to the
storage dtype, and under x64 the f64 results never round-trip through
f32.  ghostlint's GL003 checks that source code *names* the contract;
this analyzer traces the real program with ``jax.make_jaxpr`` and walks
every equation (recursing into ``pallas_call`` kernel jaxprs and
control-flow sub-jaxprs) for three violation classes:

- **narrow accumulation** — a ``dot_general``/``reduce_sum``/``cumsum``
  whose float output is below 32 bits: the reduction itself runs in the
  storage dtype;
- **downcast below compute** — a float→float ``convert_element_type``
  to a dtype narrower than both its input and the target's declared
  compute dtype: a value silently lost precision mid-flow (a *boundary*
  cast down to the compute dtype itself, e.g. an f64 Kahan dot folding
  back into f32 solver state, is legal);
- **storage round-trip** — an upcast whose operand was itself produced
  by a downcast: the tell-tale of a result bounced through a narrower
  dtype (x64 results through f32, f32 accumulators through bf16).

Findings anchor at the audited entry point's def line, so
``# ghostsan: disable=GS102`` works there.
"""
from __future__ import annotations

from typing import Any, Callable, Iterator, List, NamedTuple, Tuple

from tools.ghostsan.engine import Finding, anchor

RULE_ID = "GS102"
RULE_TITLE = ("traced dtype flow honors the storage/accumulate "
              "contract: no narrow accumulation, no downcast below the "
              "compute dtype, no storage round-trips")

_ACC_PRIMS = ("dot_general", "reduce_sum", "cumsum")


def _iter_eqns(jaxpr) -> Iterator[Any]:
    """Every equation, recursing through sub-jaxprs (scan/while/cond
    bodies, custom_jvp calls, and ``pallas_call`` kernel jaxprs)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                subj = getattr(sub, "jaxpr", None)
                if subj is not None and hasattr(subj, "eqns"):
                    yield from _iter_eqns(subj)


def _bits(dtype) -> int:
    import numpy as np
    return int(np.dtype(dtype).itemsize) * 8


def _is_float(dtype) -> bool:
    import jax.numpy as jnp
    return jnp.issubdtype(dtype, jnp.floating)


def audit_jaxpr(jaxpr, *, compute_bits: int, target: str,
                anchor_obj: Any) -> List[Finding]:
    """Walk one jaxpr for the three violation classes."""
    path, line, text = anchor(anchor_obj)

    def finding(msg: str) -> Finding:
        return Finding(rule=RULE_ID, path=path, line=line,
                       message=f"[{target}] {msg}", text=text)

    findings: List[Finding] = []
    downcasts = {}                       # outvar -> (src_bits, dst_bits)
    for eqn in _iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim in _ACC_PRIMS:
            out = eqn.outvars[0].aval
            if _is_float(out.dtype) and _bits(out.dtype) < 32:
                findings.append(finding(
                    f"narrow accumulation: {prim} reduces in "
                    f"{out.dtype} — widen the operands first "
                    f"(storage_acc_dtype) so the sum runs >= f32"))
        elif prim == "convert_element_type":
            src = eqn.invars[0].aval.dtype
            dst = eqn.outvars[0].aval.dtype
            if not (_is_float(src) and _is_float(dst)):
                continue
            sb, db = _bits(src), _bits(dst)
            if db < sb:
                downcasts[eqn.outvars[0]] = (sb, db)
                if db < compute_bits:
                    findings.append(finding(
                        f"downcast below compute dtype: {src} -> {dst} "
                        f"with declared compute width {compute_bits} "
                        f"bits — a mid-flow value lost precision"))
            elif db > sb and eqn.invars[0] in downcasts:
                osb, odb = downcasts[eqn.invars[0]]
                findings.append(finding(
                    f"storage round-trip: a {osb}-bit value was cast "
                    f"down to {odb} bits and back up to {db} — the "
                    f"intermediate narrowing silently quantized it"))
    return findings


def audit_function(fn: Callable, *example_args, compute_bits: int = 32,
                   target: str = "", anchor_obj: Any = None,
                   ) -> List[Finding]:
    """Trace ``fn(*example_args)`` and audit the resulting jaxpr.

    The public seam for seeded-bug fixtures; the in-tree audit builds
    concrete targets and funnels them through here.
    """
    import jax
    closed = jax.make_jaxpr(fn)(*example_args)
    return audit_jaxpr(closed.jaxpr, compute_bits=compute_bits,
                       target=target or getattr(fn, "__name__", "<fn>"),
                       anchor_obj=anchor_obj if anchor_obj is not None
                       else fn)


class _Target(NamedTuple):
    name: str
    fn: Callable                        # traced callable
    args: Tuple[Any, ...]
    compute_bits: int
    anchor_obj: Any                     # where the finding points


def _solver_targets(dense, *, store_dtype, tag) -> Iterator[_Target]:
    import importlib
    import numpy as np
    import jax.numpy as jnp

    from repro.core import sellcs
    from repro.solvers.operator import GhostOperator

    cg = importlib.import_module("repro.solvers.cg")
    minres = importlib.import_module("repro.solvers.minres")
    stepper = importlib.import_module("repro.solvers.stepper")

    n = dense.shape[0]
    A = sellcs.from_dense(dense, C=4, sigma=16, dtype=np.float32,
                          store_dtype=store_dtype)
    op = GhostOperator(A)
    B = jnp.ones((n, 2), jnp.float32)

    st = cg.cg_init(op, B)
    yield _Target(f"cg_step[{tag}]",
                  lambda s: cg.cg_step(op, s, 0), (st,), 32, cg.cg_step)
    mst = minres.minres_init(op, B)
    yield _Target(f"minres_step[{tag}]",
                  lambda s: minres.minres_step(op, s, 0), (mst,), 32,
                  minres.minres_step)
    # the chunked driver: the while_loop body run_chunk actually jits —
    # trace the loop itself so merge/termination plumbing is audited too
    yield _Target(
        f"run_chunk.cg[{tag}]",
        lambda s: stepper.run_chunk(op, "cg", 2, s,
                                    lambda o, x: cg.cg_step(o, x, 0)),
        (st,), 32, stepper.run_chunk)
    # shared-Krylov block steppers: the SVQB/Gram/band-QR small-matrix
    # algebra must hold the same contract as the column recurrences
    blockm = importlib.import_module("repro.solvers.block")
    bst = cg.cg_init(op, B, block=True)
    yield _Target(f"block_cg_step[{tag}]",
                  lambda s: cg.cg_step(op, s, 0), (bst,), 32,
                  blockm.block_cg_body)
    bmst = minres.minres_init(op, B, block=True)
    yield _Target(f"block_minres_step[{tag}]",
                  lambda s: minres.minres_step(op, s, 0), (bmst,), 32,
                  blockm.block_minres_body)


def iter_targets() -> Iterator[_Target]:
    """Concrete in-tree audit targets: kernel wrappers, core entry
    points, and stepper bodies, in f32 and bf16-storage flavors, plus an
    x64 flavor guarding the f64-through-f32 round-trip."""
    import numpy as np
    import jax.numpy as jnp

    from repro.core import sellcs
    from repro.core.spmv import (SpmvOpts, fused_dots, spmv_ref,
                                 storage_acc_dtype)
    from repro.kernels import ops

    n = 48
    rng = np.random.default_rng(7)
    dense = np.where(rng.random((n, n)) < 0.25,
                     rng.standard_normal((n, n)), 0.0)
    dense = dense + dense.T + np.eye(n) * 8.0      # SPD for the solvers

    opts = SpmvOpts(dot_yy=True, dot_xy=True)
    for store in (None, "bfloat16", "float16"):
        A = sellcs.from_dense(dense, C=4, sigma=16, dtype=np.float32,
                              store_dtype=store)
        x = jnp.ones((n, 2), jnp.float32)
        y = jnp.ones((n, 2), jnp.float32)
        cb = _bits(storage_acc_dtype(A.dtype))
        tag = store or "f32"
        yield _Target(f"spmv_ref[{tag}]",
                      lambda xv, yv, A=A: spmv_ref(A, xv, yv, None, opts),
                      (x, y), cb, spmv_ref)
        yield _Target(f"ops.sellcs_spmv[{tag}]",
                      lambda xv, yv, A=A: ops.sellcs_spmv(
                          A, xv, yv, opts=opts),
                      (x, y), cb, ops.sellcs_spmv)

    V = jnp.ones((40, 4), jnp.float32)
    W = jnp.ones((40, 4), jnp.float32)
    X = jnp.ones((4, 4), jnp.float32)
    yield _Target("ops.tsmttsm", lambda a, b: ops.tsmttsm(a, b), (V, W),
                  32, ops.tsmttsm)
    yield _Target("ops.tsmm", lambda a, b: ops.tsmm(a, b), (V, X),
                  32, ops.tsmm)
    yield _Target("ops.fused_axpby_dots",
                  lambda a, b: ops.fused_axpby_dots(a, b, dot_yy=True),
                  (V, W), 32, ops.fused_axpby_dots)
    yield _Target("fused_dots",
                  lambda a, b: fused_dots(a, b, opts), (V, W),
                  32, fused_dots)

    yield from _solver_targets(dense, store_dtype=None, tag="f32")
    yield from _solver_targets(dense, store_dtype="bfloat16", tag="bf16")


def _iter_x64_targets() -> Iterator[_Target]:
    import numpy as np
    import jax.numpy as jnp

    from repro.core import sellcs
    from repro.core.spmv import SpmvOpts, spmv_ref

    n = 32
    rng = np.random.default_rng(7)
    dense = np.where(rng.random((n, n)) < 0.3,
                     rng.standard_normal((n, n)), 0.0)
    dense = dense + dense.T + np.eye(n) * 8.0
    A = sellcs.from_dense(dense, C=4, sigma=8, dtype=np.float64)
    x = jnp.ones((n, 2), jnp.float64)
    opts = SpmvOpts(dot_yy=True)
    yield _Target("spmv_ref[x64]",
                  lambda xv: spmv_ref(A, xv, None, None, opts), (x,),
                  64, spmv_ref)


def run_dtype_audit(verbose: bool = False,
                    progress=None) -> List[Finding]:
    """GS102 over the in-tree targets (default-precision and x64)."""
    import jax

    from repro.core import execution

    findings: List[Finding] = []
    with execution.force(interpret=True):
        for t in iter_targets():
            if verbose and progress:
                progress(f"GS102 {t.name}")
            findings.extend(audit_function(
                t.fn, *t.args, compute_bits=t.compute_bits,
                target=t.name, anchor_obj=t.anchor_obj))
        # x64 scope: f64 results must not round-trip through f32
        with jax.enable_x64(True):
            for t in _iter_x64_targets():
                if verbose and progress:
                    progress(f"GS102 {t.name}")
                findings.extend(audit_function(
                    t.fn, *t.args, compute_bits=t.compute_bits,
                    target=t.name, anchor_obj=t.anchor_obj))
    return findings
