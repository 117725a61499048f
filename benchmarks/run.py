"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes a
``BENCH_<utc-date>.json`` artifact (per-bench rows plus execution-policy
and backend metadata) so the perf trajectory is tracked across PRs as
committed files instead of living in CI grep bars.  Wall-clock is CPU
sanity only; the graded roofline numbers come from the dry-run
artifacts (EXPERIMENTS.md §Roofline).

    PYTHONPATH=src python -m benchmarks.run [--only fig6,fig7]
    PYTHONPATH=src python -m benchmarks.run --artifact out/bench.json
    PYTHONPATH=src python -m benchmarks.run --no-artifact
"""
from __future__ import annotations

import argparse
import datetime
import importlib
import json
import os
import platform
import sys
import traceback

from benchmarks import common

BENCHES = [
    "fig5_overlap",        # task-mode overlap (Fig. 5)
    "fig6_formats",        # SELL-C-sigma vs CRS SpMV (Fig. 6)
    "fig7_tsm",            # tall-skinny kernels vs GEMM (Fig. 7)
    "fig8_layout",         # row- vs col-major block vectors (Fig. 8)
    "fig9_vectorization",  # width-tile sweep (Fig. 9)
    "fig10_codegen",       # hard-coded block width (Fig. 10)
    "fig11_scaling",       # Krylov case study + scaling model (Fig. 11)
    "table_hetero",        # heterogeneous weighted SpMV (section 4.1)
    "table_construction",  # construction cost (section 5.1)
    "fig_kpm_fusion",      # KPM fusion gain (section 5.3 / [24])
    "table_serving",       # continuous-batching SolverService (C2+C5)
    "table_precond",       # block-Jacobi / Chebyshev preconditioned CG
    "table_mixed_precision",  # bf16/f32 storage vs f32/f64 accumulate (C6)
    "table_block_krylov",  # shared-Krylov block CG/MINRES vs column steppers
]


def _default_artifact_path() -> str:
    date = datetime.datetime.now(datetime.timezone.utc).strftime("%Y-%m-%d")
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), f"BENCH_{date}.json")


def _metadata() -> dict:
    import jax
    from repro.core import execution
    return {
        "utc_time": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "execution_policy": execution.describe(),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "python": platform.python_version(),
        "platform": platform.platform(),
    }


def write_artifact(path: str, *, benches: dict, failed: list,
                   metadata: dict) -> None:
    data = {
        "comment": ("benchmark trajectory artifact; regenerate with "
                    "PYTHONPATH=src python -m benchmarks.run.  Wall-"
                    "clock rows are CPU sanity numbers — the derived "
                    "column carries the roofline model quantities."),
        "metadata": metadata,
        "benches": benches,
        "failed": failed,
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1)
        f.write("\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench name filter")
    ap.add_argument("--artifact", default=None, metavar="PATH",
                    help="artifact path (default: BENCH_<utc-date>.json "
                         "at the repo root)")
    ap.add_argument("--no-artifact", action="store_true",
                    help="skip writing the JSON artifact")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.core import execution
    execution.use_compile_cache(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    print("name,us_per_call,derived")
    print(f"execution_policy,0.0,{execution.describe()}")
    benches: dict = {}
    failed = []
    for name in BENCHES:
        if only and not any(name.startswith(o) for o in only):
            continue
        common.reset_rows()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            mod.main()
            benches[name] = list(common.ROWS)
        except Exception as e:                            # noqa: BLE001
            failed.append(name)
            print(f"{name},0.0,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if not args.no_artifact:
        path = args.artifact or _default_artifact_path()
        write_artifact(path, benches=benches, failed=failed,
                       metadata=_metadata())
        print(f"artifact,0.0,{path}")
    if failed:
        sys.exit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
