"""Run one cell of the chip benchmark once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, last, ``checks``: each number that decided ``correct``
beside its limit.  The same numbers are the last lines of standard error.
A platform other than ``tpu``, too few chips, or a kernel that falls
back to its reference is an error: the run exits non-zero and prints no
result.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import warnings  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    import harness
    import work

    c = harness.cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{devices[0].platform!r})")
    if len(devices) < c.chips:
        raise SystemExit(f"bench: {args.workload} needs {c.chips} chips, "
                         f"JAX found {len(devices)}")
    kind = devices[0].device_kind
    peaks = work.peaks(kind)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import execution

    cache_dir = execution.use_compile_cache(str(ROOT))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # a kernel timed as its reference would be a silent lie
    warnings.filterwarnings("error", message=".*falling back.*",
                            category=RuntimeWarning)
    policy = execution.describe()
    if not policy.startswith("mode=compiled;backend=tpu"):
        raise SystemExit(f"bench: execution policy is {policy}")
    harness.log(workload=args.workload, seed=args.seed, policy=policy,
                compile_cache=cache_dir, jax=jax.__version__)

    r = harness.run_cell(c, args.seed, args.seconds, bool(args.trace),
                         devices=devices[:c.chips], started=STARTED,
                         peaks=peaks)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": r["memory_peak_bytes"]}
    line = {"correct": r["correct"], "attempted": r["attempted"],
            "failed": r["failed"], "metrics": r["metrics"],
            "device": device}
    if args.trace:
        t = r["trace"]
        if t is None:
            raise SystemExit("bench: the trace holds no device operation")
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        line["breakdown"] = {"device_ops": t["device_ops"],
                             "idle_gaps": t["idle_gaps"]}
    line["checks"] = r["checks"]
    for name, c_ in r["checks"].items():
        print(f"check {name} {c_['value']!r} limit {c_['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
