"""Shared helpers of the benchmark's CPU tests: the cells of
``BENCHMARK.json``, and the cells whose files the benchmark keeps for a
later entry, cut to a size a test run holds."""
import dataclasses
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402

#: a cell whose configuration, traffic and metric files are kept under
#: the benchmark's directory, but not yet listed in ``BENCHMARK.json``
SPARE = {
    "configs": [{"name": "hpcg27_104",
                 "file": "bench/configs/hpcg27_104.json"}],
    "workloads": [{"name": "hpcg27_104.bjacobi16_cg_w8",
                   "config": "hpcg27_104", "traffic": "bjacobi16_cg_w8",
                   "chips": 1}],
    "per_layer": [{"name": "block_diag_roofline", "unit": "%",
                   "source": "device_trace",
                   "workloads": ["hpcg27_104.bjacobi16_cg_w8"]}],
}
CELLS = ("poisson7_128.cg_w8", "hpcg27_104.bjacobi16_cg_w8")
TINY_NX = {"laplace7": 16, "hpcg27": 12}
#: the v5e row of the peaks table, so that roofline readers run on a CPU
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def bench() -> dict:
    """``BENCHMARK.json`` with the spare cell added."""
    b = harness.load_benchmark()
    for key, extra in SPARE.items():
        b[key] = b[key] + extra
    return b


def tiny(name: str, **config) -> harness.Cell:
    """Cell ``name`` on a grid of a few thousand rows, with a short grace."""
    c = harness.cell(name, bench())
    gen = c.config["matrix"]["generator"]
    cfg = dict(c.config, matrix=dict(c.config["matrix"], nx=TINY_NX[gen]),
               n=None, nnz=None)
    cfg.update(config)
    return dataclasses.replace(c, config=cfg,
                               traffic=dict(c.traffic, grace_s=2.0))


def run(c: harness.Cell, *, seed: int = 2 ** 31 + 5, seconds: float = 1.0,
        trace: bool = False) -> dict:
    import jax
    return harness.run_cell(c, seed, seconds, trace,
                            devices=jax.devices()[:c.chips],
                            started=time.perf_counter(), peaks=PEAKS)
