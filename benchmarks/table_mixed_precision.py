"""Mixed-precision SELL-C-sigma storage: SpMV + CG across storage dtypes.

The paper's C6 argument applied to *data types*: SpMV is memory-bandwidth
bound (section 5.1, Fig. 6), so narrowing the value stream is a direct
speedup — GHOST generates kernels per dtype for exactly this reason.  This
bench runs the same 3D Laplacian at three storage configurations:

    f64          — f64 values, f64 accumulate (requires x64)
    f32          — f32 values, f32 accumulate (the classic single dtype)
    bf16_store   — bf16 *stored* values, f32 accumulate (store_dtype=)

and reports, per variant: bytes moved per nonzero (value + column index,
beta-adjusted), SpMV wall time, CG iterations to tolerance, and the final
residual.  The acceptance bar — bf16 storage >= 1.3x faster than f32
storage for SpMV — is asserted only when the *compiled* Pallas path
actually ran (on CPU/interpret runs the value-stream width does not bound
throughput, so the ratio is reported but not asserted).

CG must converge at every storage dtype; the iteration delta vs f32 is the
price of the narrower values (typically 0-15% on a Laplacian at 1e-6).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import policy_row, row, time_fn
from repro.core import execution
from repro.core.sellcs import from_coo
from repro.kernels import ops
from repro.matrices import laplace3d
from repro.solvers import cg, make_operator

NX = 12                    # n = 1728
TOL = 1e-6
MAXITER = 2000
NVECS = 4                  # block vector: the high-intensity sweep (C2)


def _bytes_per_nnz(m) -> float:
    """HBM bytes per nonzero of one SpMV value+index stream (beta-adj)."""
    vb = jnp.dtype(m.store_dtype).itemsize
    ib = jnp.dtype(m.cols.dtype).itemsize
    return (vb + ib) * m.cap / max(1, m.nnz)


def _run_variant(name, r, c, v, n, *, dtype, store_dtype, impl):
    m = from_coo(r, c, v, (n, n), C=16, sigma=32, w_align=4,
                 dtype=dtype, store_dtype=store_dtype)
    op = make_operator(m, impl=impl)
    rng = np.random.default_rng(7)
    x = m.permute(jnp.asarray(rng.standard_normal((n, NVECS)), m.dtype))
    spmv_t = time_fn(lambda: op.mv(x), warmup=2, iters=5)

    b = m.permute(jnp.asarray(rng.standard_normal(n), m.dtype))
    res = cg(op, b, tol=TOL, maxiter=MAXITER)
    conv = bool(np.all(np.asarray(res.converged)))
    assert conv, f"CG did not converge at storage variant {name!r}"
    row(f"mixed_precision_spmv_{name}", spmv_t * 1e6,
        f"n={n};nvecs={NVECS};store={m.store_dtype};compute={m.dtype};"
        f"bytes_per_nnz={_bytes_per_nnz(m):.2f};beta={m.beta:.3f}")
    row(f"mixed_precision_cg_{name}", 0.0,
        f"iters={int(res.iters)};tol={TOL:g};"
        f"resnorm={float(np.max(res.resnorm)):.3e};converged={conv}")
    return spmv_t, int(res.iters)


def main():
    policy_row("table_mixed_precision")
    r, c, v, n = laplace3d(NX)
    # the raw stencil values (+-1, 6) are exactly representable in bf16,
    # which would make the accuracy leg vacuous; an irrational uniform
    # scale keeps the matrix SPD while every stored value genuinely
    # rounds at the storage width
    v = v * np.e

    # compiled Pallas when the backend takes it, jnp reference otherwise
    # (an interpret-mode Pallas sweep would time the interpreter, not the
    # value stream)
    pol = execution.current_policy()
    compiled = (not pol.interpret) and execution.compiled_available()
    impl = "pallas" if compiled else "ref"

    times, iters = {}, {}
    try:
        with jax.enable_x64(True):
            times["f64"], iters["f64"] = _run_variant(
                "f64", r, c, v, n, dtype=np.float64, store_dtype=None,
                impl=impl)
    except Exception as e:                               # noqa: BLE001
        row("mixed_precision_spmv_f64", 0.0, f"SKIPPED:{type(e).__name__}")
    times["f32"], iters["f32"] = _run_variant(
        "f32", r, c, v, n, dtype=np.float32, store_dtype=None, impl=impl)
    times["bf16_store"], iters["bf16_store"] = _run_variant(
        "bf16_store", r, c, v, n, dtype=np.float32,
        store_dtype=jnp.bfloat16, impl=impl)

    speedup = times["f32"] / times["bf16_store"]
    delta = iters["bf16_store"] - iters["f32"]
    row("mixed_precision_speedup", 0.0,
        f"bf16_store_vs_f32={speedup:.2f}x;cg_iter_delta={delta:+d};"
        f"compiled={compiled};asserted={compiled}")
    if compiled:
        # the tentpole acceptance bar: narrower values must pay off when
        # the bandwidth-bound compiled kernel actually runs
        assert speedup >= 1.3, (
            f"bf16-store SpMV speedup {speedup:.2f}x < 1.3x acceptance "
            f"bar in compiled mode")


if __name__ == "__main__":
    main()
