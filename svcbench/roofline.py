"""Peaks by device kind, and the logical work of the kernels the cells time.

The work of an operation is counted from the shapes of its logical inputs
and outputs — valid rows, not the arenas, tiles or padding a kernel uses —
so a later kernel that does the same job is read against the same work.

Roofline share = least time / measured device time, where the least time
is the larger of bytes / peak bandwidth and operations / peak rate.
"""

from __future__ import annotations

from typing import Dict, Tuple

# Google Cloud documentation, "TPU v5e" (system architecture page): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  The MXU's f32
# dots at HIGHEST precision run several bf16 passes, so their f32 rate is
# below the bf16 peak; no f32 figure is published, and using the bf16 peak
# for the compute term can only lower a share, never raise it.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
}
PEAKS_SOURCE = 'Google Cloud documentation, "TPU v5e"'

F32 = 4
BOOL = 1


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add its "
                       f"published HBM bandwidth and peak rate to PEAKS")
    return PEAKS[device_kind]


def multi_agg_work(rows: int, cols: int, queries: int, preds: int,
                   two_sided: bool) -> Tuple[float, float]:
    """(bytes, operations) of one batched moment pass.

    Inputs per side: the (rows, cols) f32 column panel, the row mask
    (bool), the 1/π weights and 1−π factors (f32); the (1+P)·C × Q column
    selectors and (2+4P) × Q bounds; output 12 × Q f32 moments.  Per row,
    query and side: the predicate tests (2 per term), the weighting and
    the four moment sums (count, Σt, Σt², Σ(1−π)t²) — 8 + 2P operations;
    a two-sided pass adds the diff and its three sums (5)."""
    sides = 2 if two_sided else 1
    side_bytes = rows * (cols * F32 + BOOL + 2 * F32)
    small = ((1 + preds) * cols + 2 + 4 * preds + 12) * queries * F32
    ops = rows * queries * (sides * (8 + 2 * preds) + (5 if two_sided else 0))
    return float(sides * side_bytes + small), float(ops)


def share_pct(bytes_: float, ops: float, device_s: float,
              device_kind: str) -> float:
    """Least time over measured time, in percent."""
    p = peaks(device_kind)
    least = max(bytes_ / p["hbm_bytes_per_s"], ops / p["flops_per_s"])
    return 100.0 * least / device_s
