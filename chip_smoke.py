"""End-to-end smoke run of the solve service on a TPU chip.

One chip (the default): generate the 7-point Laplacian ``laplace3d(128)``
(n = 2,097,152 rows, nnz = 14,581,760), register it in a
``MatrixRegistry`` as float32 SELL-C-sigma (C = 128, sigma = 1,
w_align = 8) and drain a ``SolverService`` phase by phase: 4 CG and
2 MINRES requests, 1 CG preconditioned with ``block_jacobi:16`` (the
block-diagonal kernel) and 1 block CG at width 8 (the tsmm and Kahan
tsmttsm kernels).  Then the warm service drains all eight requests at
once.  Every solution is checked against a float64 SciPy CSR product
built from the same COO.

``--chips 4`` runs only the row-distributed CG: a ``HeterogeneousEngine``
over a 4-device mesh, whose halo exchange overlaps the local SpMV, and
the one-chip ``GhostOperator`` CG it is compared with.

    python chip_smoke.py                  # one chip
    python chip_smoke.py --chips 4        # four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python chip_smoke.py --rehearse --chips 4

Prints one JSON object per line.  The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without ``--rehearse`` (which shrinks the matrix to laplace3d(16) and
accepts any platform) a platform other than "tpu" is an error.  A failed
check exits non-zero before the last line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

TOL = 1e-5        # solver tolerance on the recurrence residual, relative
RELRES = 1e-4     # bound on the true float64 relative residual
MAXITER = 3000
MATRIX = "laplace3d"

#: stacked per-shard arrays of a DistSellCS, each split over the mesh axis
SHARDED = ("l_vals", "l_cols", "l_off", "l_len", "l_rowids", "r_vals",
           "r_cols", "r_off", "r_len", "r_rowids", "send_idx", "halo_idx",
           "g2l")


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


class CacheEvents:
    """Counts compiles that consulted JAX's persistent compile cache, and
    the hits among them."""

    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "compiles",
              "/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self):
        import jax.monitoring
        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, before: dict) -> dict:
        return {k: self.counts[k] - before[k] for k in self.counts}


def true_relres(A64, b, x) -> float:
    """||b - A x|| / ||b|| in float64."""
    b = np.asarray(b, np.float64)
    r = b - A64 @ np.asarray(x, np.float64)
    return float(np.linalg.norm(r) / np.linalg.norm(b))


def laplace_system(nx: int):
    """The COO matrix, its float64 CSR reference and the build time."""
    from scipy.sparse import csr_matrix
    from repro.matrices import laplace3d

    t0 = time.perf_counter()
    r, c, v, n = laplace3d(nx)
    generate_s = time.perf_counter() - t0
    A64 = csr_matrix((np.asarray(v, np.float64), (r, c)), shape=(n, n))
    return (r, c, v, n), A64, generate_s


def kernel_temp_bytes(n: int) -> dict:
    """Temporary device memory of the narrow-width kernels at n rows."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    cases = {
        "tsmm_b8": (lambda V, X: ops.tsmm(V, X), (S(n, 8), S(8, 8))),
        "tsmttsm_kahan_b8": (lambda V, W: ops.tsmttsm(V, W, kahan=True),
                             (S(n, 8), S(n, 8))),
        "fused_axpby_dots_b1": (
            lambda x, y: ops.fused_axpby_dots(x, y, 1.0, 1.0, dot_yy=True),
            (S(n, 1), S(n, 1))),
        "block_jacobi_bs16": (lambda B, x: ops.block_jacobi_apply(B, x),
                              (S(n // 16, 16, 16), S(n, 1))),
    }
    return {name: int(jax.jit(fn).lower(*shapes).compile()
                      .memory_analysis().temp_size_in_bytes)
            for name, (fn, shapes) in cases.items()}


def check_tickets(name: str, tickets, rhs, A64) -> dict:
    iters, relres = [], []
    for t, b in zip(tickets, rhs):
        check(t.status == "done" and t.result.converged,
              f"{name}: request {t.id} ended {t.status}, converged="
              f"{t.result.converged if t.result else None}")
        rr = true_relres(A64, b, t.result.x)
        check(np.isfinite(rr) and rr <= RELRES,
              f"{name}: true relative residual {rr} > {RELRES}")
        iters.append(int(t.result.iters))
        relres.append(rr)
    return {"iterations": iters, "true_relres": relres}


def one_chip(nx: int, seed: int, cache: CacheEvents) -> None:
    from repro.runtime import MatrixRegistry, SolverService

    (r, c, v, n), A64, generate_s = laplace_system(nx)
    t0 = time.perf_counter()
    reg = MatrixRegistry()
    reg.register(MATRIX, rows=r, cols=c, vals=v, shape=(n, n), C=128,
                 sigma=1, w_align=8, dtype=np.float32)
    e = reg.entry(MATRIX)
    emit(phase="setup", n=int(n), nnz=int(len(v)), generate_s=generate_s,
         register_s=time.perf_counter() - t0, spmv_path=e.spmv_path,
         steps_per_chunk=e.steps_per_chunk,
         slot_rows=reg.stats["spmv_window_slot_rows"])

    svc = SolverService(reg, block_width=8)
    rng = np.random.default_rng(seed)
    phases = (("cg", {"solver": "cg"}, 4),
              ("minres", {"solver": "minres"}, 2),
              ("cg_block_jacobi16",
               {"solver": "cg", "precond": "block_jacobi:16"}, 1),
              ("block_cg_w8", {"solver": "cg", "block": True}, 1))
    warm = []
    for name, kw, count in phases:
        rhs = rng.standard_normal((count, n)).astype(np.float32)
        warm.append((name, kw, rng.standard_normal((count, n))
                     .astype(np.float32)))
        tickets = [svc.submit(MATRIX, b, tol=TOL, maxiter=MAXITER, **kw)
                   for b in rhs]
        before = cache.snapshot()
        t0 = time.perf_counter()
        svc.step()                  # opens the batch: compiles + one chunk
        first_step_s = time.perf_counter() - t0
        cache_first = cache.since(before)
        t0 = time.perf_counter()
        svc.drain()
        steady_s = time.perf_counter() - t0
        res = check_tickets(name, tickets, rhs, A64)
        steady_iters = max(res["iterations"]) - svc.chunk_iters
        emit(phase=name, requests=count, first_step_s=first_step_s,
             first_step_compile_cache=cache_first, steady_s=steady_s,
             steady_s_per_iter=(steady_s / steady_iters
                                if steady_iters > 0 else None),
             chunk_iters=svc.chunk_iters, **res)

    # the warm service holds all eight requests at once
    tickets = [(name, b, svc.submit(MATRIX, b, tol=TOL, maxiter=MAXITER,
                                    **kw))
               for name, kw, rhs in warm for b in rhs]
    before = cache.snapshot()
    t0 = time.perf_counter()
    svc.drain()
    drain_s = time.perf_counter() - t0
    per_phase = {}
    for name, _, _ in warm:
        mine = [(b, t) for n_, b, t in tickets if n_ == name]
        per_phase[name] = check_tickets(name, [t for _, t in mine],
                                        [b for b, _ in mine], A64)
    emit(phase="service_drain_warm", requests=len(tickets), drain_s=drain_s,
         compile_cache=cache.since(before), stats=svc.stats, **per_phase)
    emit(phase="kernel_temp_bytes", n=int(n), **kernel_temp_bytes(int(n)))


def four_chips(nx: int, seed: int, cache: CacheEvents, rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import from_coo
    from repro.runtime import DevicePool, HeterogeneousEngine
    from repro.solvers import cg, make_operator

    devices = jax.devices()[:4]
    (r, c, v, n), A64, generate_s = laplace_system(nx)
    pool = DevicePool.detect(devices)
    kinds = [(cl.name, cl.count) for cl in pool.classes]
    check(rehearse or kinds == [("TPU v5 lite", 4)],
          f"DevicePool.detect gave {kinds}, want 4 x 'TPU v5 lite'")
    t0 = time.perf_counter()
    eng = HeterogeneousEngine(r, c, v, n, mesh=Mesh(np.array(devices),
                                                    ("data",)),
                              pool=pool, C=128, sigma=1, w_align=8,
                              dtype=np.float32)
    build_s = time.perf_counter() - t0
    check(np.allclose(eng.plan.weights, 0.25),
          f"split weights {eng.plan.weights} are not equal")
    for field in SHARDED:
        arr = getattr(eng.A, field)
        placed = {s.device: s.index[0] for s in arr.addressable_shards}
        want = {d: slice(i, i + 1) for i, d in enumerate(devices)}
        check(placed == want,
              f"{field} shards are not one per device: {placed}")
    emit(phase="setup", n=int(n), nnz=int(len(v)), generate_s=generate_s,
         engine_build_s=build_s, pool=repr(pool),
         shard_rows=[e - s for s, e in eng.plan.ranges])

    b = np.random.default_rng(seed).standard_normal(n).astype(np.float32)
    one = make_operator(from_coo(r, c, v, (n, n), C=128, sigma=1, w_align=8,
                                 dtype=np.float32))
    results = {}
    # the one-chip reference runs once: its time is not what is compared
    for name, op, runs in (("cg_4chips_overlap", eng.operator(overlap=True),
                            ("first", "warm")),
                           ("cg_1chip", one, ("first",))):
        bop = op.to_op_space(jnp.asarray(b))
        times = {}
        for run in runs:
            before = cache.snapshot()
            t0 = time.perf_counter()
            res = cg(op, bop, tol=TOL, maxiter=MAXITER)
            jax.block_until_ready(res.x)
            times[f"{run}_s"] = time.perf_counter() - t0
            times[f"{run}_compile_cache"] = cache.since(before)
        x = np.asarray(op.from_op_space(res.x))
        rr = true_relres(A64, b, x)
        iters = int(res.iters)
        check(bool(np.all(res.converged)), f"{name} did not converge")
        check(np.isfinite(rr) and rr <= RELRES,
              f"{name}: true relative residual {rr} > {RELRES}")
        results[name] = iters
        emit(phase=name, iterations=iters, true_relres=rr, **times)
    d_it = results["cg_4chips_overlap"] - results["cg_1chip"]
    check(abs(d_it) <= 2, f"iteration counts differ by {d_it}: {results}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="laplace3d(16) on any platform (CPU rehearsal)")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{d0.platform!r}); --rehearse runs a small size")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, JAX found {len(devices)}")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import execution

    cache_dir = execution.use_compile_cache(str(ROOT))
    # a kernel timed as its jnp fallback would be a silent lie
    warnings.filterwarnings("error", message=".*falling back.*",
                            category=RuntimeWarning)
    cache = CacheEvents()
    policy = execution.describe()
    emit(execution=policy, jax=jax.__version__, compile_cache_dir=cache_dir)
    if not args.rehearse:
        check(policy.startswith("mode=compiled;backend=tpu"),
              f"execution policy is {policy}")

    nx = 16 if args.rehearse else 128
    if args.chips == 4:
        four_chips(nx, args.seed, cache, args.rehearse)
    else:
        one_chip(nx, args.seed, cache)
    emit(peak_bytes_in_use=[(d.memory_stats() or {}).get("peak_bytes_in_use")
                            for d in devices[:args.chips]])
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
