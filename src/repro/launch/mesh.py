"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
*before* the first jax initialization.

Production target: TPU v5e pods, 256 chips each (16x16), 2 pods for the
multi-pod dry-run.  Axes:
    pod    — data parallelism across DCN-connected pods
    data   — FSDP/batch within a pod
    model  — tensor/expert parallelism within a pod
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_host_mesh", "HW"]


# TPU v5e (device_kind "TPU v5 lite") per-chip peaks, from the Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s, and 1,600 Gbit/s of inter-chip interconnect over 4 links.
# The one table of device peaks: runtime.devicepool keys its v5e entry
# on it.
HW = {
    "peak_flops_bf16": 197e12,   # FLOP/s
    "hbm_bw": 819e9,             # B/s
    "ici_bw": 50e9,              # B/s per link (1,600 Gbit/s / 4)
    "hbm_bytes": 16e9,
}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    import numpy as np
    devs = np.array(jax.devices()[: data * model]).reshape(data, model)
    return jax.sharding.Mesh(devs, ("data", "model"))
