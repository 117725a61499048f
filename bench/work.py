"""Compulsory work of each kernel, from shapes alone, and the roofline.

The bytes count what the kernel has to move at least once: every stored
nonzero (value and column index), every vector element read and every
result element written.  Padding slots of a SELL-C-sigma layout are not
compulsory, so ``nnz`` is the count of true nonzeros.  The counts do not
depend on which implementation runs the kernel.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import NamedTuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class Work(NamedTuple):
    bytes: float
    flops: float


def spmv(nnz: int, n: int, b: int, *, val_bytes: int = 4,
         idx_bytes: int = 4, vec_bytes: int = 4) -> Work:
    """``Y = A X`` with ``A`` of ``nnz`` nonzeros on ``n`` rows and ``X``,
    ``Y`` of ``b`` columns."""
    return Work(bytes=nnz * (val_bytes + idx_bytes) + 2 * n * b * vec_bytes,
                flops=2 * nnz * b)


def block_diag(n: int, bs: int, b: int, *, val_bytes: int = 4,
               vec_bytes: int = 4) -> Work:
    """``Y = diag(B_0, ..., B_k) X`` with dense ``bs x bs`` blocks over
    ``n`` rows and ``X``, ``Y`` of ``b`` columns."""
    return Work(bytes=n * bs * val_bytes + 2 * n * b * vec_bytes,
                flops=2 * n * bs * b)


def peaks(kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of one chip of ``kind`` (its ``device_kind``).
    A kind that the table does not hold is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} "
                       f"(table has {sorted(table)})")
    return table[kind]


def roofline_pct(work: Work, seconds: float, peak: dict) -> float:
    """Share of the roofline: the least time the chip needs for ``work``
    (bytes at peak bandwidth or operations at peak rate, the larger)
    over the time measured."""
    least = max(work.bytes / peak["hbm_bytes_per_s"],
                work.flops / peak["bf16_flops_per_s"])
    return 100.0 * least / seconds
