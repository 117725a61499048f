"""Readings that a cell's limits are set from, in one process.

    python bench/limits.py --workload <name> --seconds 30 \
        --seeds 101 102 103 --control-seeds 201 202 203

For each seed of ``--seeds`` the program serves the cell's traffic (ramp,
window, the wait for answers due) on one registered matrix and every
answer is checked, as in a run; the line printed holds the numbers
compared.  Between seeds the open requests are cancelled.  The control is
the program's own lower-precision path: the same matrix registered with
``bfloat16`` storage and compute, so that the vectors and the CG
recurrence run in bfloat16 too, served for each of ``--control-seeds``.
The last line gives the lower reading (the largest worst residual of the
program's seeds) and the upper one (the smallest of the control's).
Runs on the chip only, like ``run.py``; the benchmark's runs never call
this.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def readings(c, devices, seeds, seconds, label):
    import harness
    out = []
    p = harness.prepare(c, devices, STARTED)
    for seed in seeds:
        served = harness.serve(p, seed, seconds)
        checks = harness.check(p.coo, served.requests,
                               float(c.traffic["relres_limit"]))
        harness.quiesce(p.svc, served.clients)
        iters = [r.chunks * p.svc.chunk_iters for r in served.requests
                 if r.ticket.result is not None]
        line = {"label": label, "seed": seed, "correct": checks.correct,
                "attempted": checks.attempted, "failed": checks.failed,
                "iters": [min(iters, default=None), max(iters, default=None)],
                **{k: v["value"] for k, v in checks.numbers().items()}}
        print(json.dumps(line), flush=True)
        out.append(checks.worst_relres)
    del p
    gc.collect()
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    import harness
    import jax

    c = harness.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < c.chips:
        raise SystemExit(f"limits: needs {c.chips} TPU chips, JAX found "
                         f"{devices}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import execution
    execution.use_compile_cache(str(ROOT))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devices = devices[:c.chips]
    lower = readings(c, devices, args.seeds, args.seconds, "program")
    control = dataclasses.replace(c, config=dict(
        c.config, storage=dict(c.config["storage"], dtype="bfloat16")))
    upper = readings(control, devices, args.control_seeds, args.seconds,
                     "control_bfloat16")
    print(json.dumps({"workload": args.workload,
                      "lower": max(lower, default=None),
                      "upper": min(upper, default=None),
                      "seconds_total": time.perf_counter() - STARTED}))


if __name__ == "__main__":
    main()
