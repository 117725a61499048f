"""Device classification and roofline-weighted throughput (GHOST 4.1).

GHOST assigns each process a *weight* proportional to the device's
attainable memory bandwidth, because SpMV is bandwidth-bound at its code
balance (6 bytes/flop for double + 32-bit indices).  ``DevicePool``
reproduces that policy on a jax platform: it groups ``jax.devices()`` into
classes by ``device_kind``, attaches per-class bandwidth/peak-flop specs
from a table of published numbers (a kind missing from it is an error,
never a guess), and turns :func:`repro.launch.costmodel.spmv_cost`
roofline terms into per-device throughput estimates -> split weights.

The weights are *estimates to start from*; the engine's rebalance loop
(:meth:`repro.runtime.split.SplitPlan.rebalance`) refines them online from
measured per-shard SpMV times, which is how GHOST tolerates model error
("automatic performance-model-guided data distribution ... corrected by
runtime measurements").
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.launch.costmodel import Cost, spmv_cost
from repro.launch.mesh import HW

__all__ = ["DeviceClass", "DevicePool", "KNOWN_DEVICE_SPECS"]


# mem_bw in B/s, peak_flops in FLOP/s, keyed by the exact (lower-cased)
# ``device_kind`` that JAX reports.  "TPU v5 lite" is the TPU v5e, whose
# published peaks (and their source) live in launch.mesh.HW.  The CPU/GPU/
# PHI entries are the paper's Table 1 reference node (Emmy: SNB socket
# 50 GB/s, K20 GPU and Xeon Phi ~150 GB/s each), so the paper's
# experiments are expressible as a pool; host CPU devices report the
# kind "cpu".
KNOWN_DEVICE_SPECS: Dict[str, Dict[str, float]] = {
    "tpu v5 lite": dict(mem_bw=HW["hbm_bw"],
                        peak_flops=HW["peak_flops_bf16"]),
    "gpu":     dict(mem_bw=150e9, peak_flops=1.17e12),   # paper's K20
    "phi":     dict(mem_bw=150e9, peak_flops=1.0e12),    # paper's Xeon Phi
    "cpu":     dict(mem_bw=50e9, peak_flops=0.43e12),    # paper's SNB socket
}


@dataclasses.dataclass(frozen=True)
class DeviceClass:
    """One class of identical devices inside a pool."""

    name: str                 # device_kind, e.g. "TPU v5 lite", "cpu"
    count: int                # devices of this class (contiguous in pool order)
    mem_bw: float             # memory bandwidth, B/s
    peak_flops: float         # peak compute, FLOP/s

    def time_for(self, cost: Cost) -> float:
        """Roofline execution-time estimate of ``cost`` on ONE device."""
        t_mem = cost.hbm_bytes / self.mem_bw
        t_cmp = cost.flops / self.peak_flops
        return max(t_mem, t_cmp)

    def spmv_throughput(self, cost: Cost) -> float:
        """Attainable flop rate on ``cost`` (bandwidth-bound for SpMV)."""
        return cost.flops / max(self.time_for(cost), 1e-30)


def _lookup_spec(kind: str) -> Dict[str, float]:
    """Specs of one device kind; an unknown kind raises."""
    try:
        return KNOWN_DEVICE_SPECS[kind.lower()]
    except KeyError:
        raise ValueError(
            f"no published specs for device kind {kind!r} (known: "
            f"{sorted(KNOWN_DEVICE_SPECS)}); add the part to "
            f"KNOWN_DEVICE_SPECS with its source") from None


class DevicePool:
    """An ordered pool of devices grouped into weighted classes.

    Order matters: device ``i`` of the pool is device ``i`` of the mesh
    axis the engine shards over, so ``device_weights()`` lines up with
    shard ids.
    """

    def __init__(self, classes: Sequence[DeviceClass]):
        if not classes:
            raise ValueError("empty device pool")
        self.classes = tuple(classes)

    # ------------------------------------------------------------- build
    @classmethod
    def detect(cls, devices=None) -> "DevicePool":
        """Classify ``jax.devices()`` (or an explicit list) by device_kind."""
        import jax
        devices = list(jax.devices()) if devices is None else list(devices)
        classes: List[DeviceClass] = []
        for d in devices:
            kind = getattr(d, "device_kind", None) or d.platform
            if classes and classes[-1].name == kind:
                classes[-1] = dataclasses.replace(
                    classes[-1], count=classes[-1].count + 1)
            else:
                spec = _lookup_spec(kind)
                classes.append(DeviceClass(name=kind, count=1, **spec))
        return cls(classes)

    @classmethod
    def from_bandwidths(cls, bws: Sequence[float], *,
                        names: Optional[Sequence[str]] = None,
                        peak_flops: float = 1e12) -> "DevicePool":
        """Synthetic pool, one device per bandwidth entry (GB/s accepted:
        values < 1e6 are treated as GB/s).  Used by benchmarks/tests to
        reproduce the paper's CPU(50) + GPU(150) + PHI(150) node."""
        classes = []
        for i, bw in enumerate(bws):
            bw = float(bw) * (1e9 if bw < 1e6 else 1.0)
            name = names[i] if names else f"dev{i}"
            classes.append(DeviceClass(name=name, count=1, mem_bw=bw,
                                       peak_flops=peak_flops))
        return cls(classes)

    # ------------------------------------------------------------ queries
    @property
    def ndevices(self) -> int:
        return sum(c.count for c in self.classes)

    def device_classes(self) -> List[DeviceClass]:
        """Per-device class, expanded in pool order (len == ndevices)."""
        out: List[DeviceClass] = []
        for c in self.classes:
            out.extend([c] * c.count)
        return out

    def device_weights(self, *, nnz: int = 0, nrows: int = 0,
                       val_bytes: int = 4, idx_bytes: int = 4,
                       nvecs: int = 1) -> np.ndarray:
        """Per-device split weights ~ attainable SpMV throughput.

        With no matrix statistics this degrades to pure bandwidth
        proportionality (the paper's default).  With ``nnz``/``nrows`` the
        weight uses the full roofline (a compute-starved device class can
        cap below its bandwidth share for very wide block vectors).
        """
        if nnz and nrows:
            cost = spmv_cost(nnz, nrows, val_bytes=val_bytes,
                             idx_bytes=idx_bytes, nvecs=nvecs)
            w = [c.spmv_throughput(cost) for c in self.device_classes()]
        else:
            w = [c.mem_bw for c in self.device_classes()]
        w = np.asarray(w, np.float64)
        return w / w.sum()

    def aggregate_spmv_gflops(self, *, val_bytes: int = 8,
                              idx_bytes: int = 4, nvecs: int = 1,
                              nnzr: float = 64.0) -> float:
        """Predicted aggregate Gflop/s at the SpMV code balance — the
        paper's Table 1 prediction (sum of bw / 6 bytes-per-flop)."""
        nnz = int(nnzr * 1000)
        cost = spmv_cost(nnz, 1000, val_bytes=val_bytes,
                         idx_bytes=idx_bytes, nvecs=nvecs)
        return sum(c.spmv_throughput(cost) for c in self.device_classes()) / 1e9

    def __repr__(self) -> str:
        parts = ", ".join(f"{c.count}x{c.name}@{c.mem_bw / 1e9:.0f}GB/s"
                          for c in self.classes)
        return f"DevicePool({parts})"
