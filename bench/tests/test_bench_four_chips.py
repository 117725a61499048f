"""A four-chip configuration is data only: a tiny matrix registered as a
``HeterogeneousEngine`` over four virtual CPU devices runs through the
same cell loop (in a fresh interpreter, which the device count needs)."""
import json
import os
import subprocess
import sys

from benchtiny import BENCH

SCRIPT = r"""
import dataclasses, json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
from benchtiny import run, tiny
assert len(jax.devices()) == 4, jax.devices()
c = tiny("poisson7_128.cg_w8", chips=4, engine={"overlap": True})
c = dataclasses.replace(c, chips=4)
r = run(c)
print(json.dumps({k: r[k] for k in ("correct", "attempted", "failed",
                                    "checks", "metrics")}))
"""


def test_engine_configuration_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(BENCH / "tests"),
         str(BENCH.parent / "src")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["correct"], r
    assert r["attempted"] > 8
    assert r["metrics"]["rhs_per_s"]["value"] > 0
