"""Peaks of each chip, and the work each kernel and each round requires.

The work is what the algorithm needs, computed from the shapes alone,
so it reads the same whatever the program does to carry it out: bins
are counted at one byte each (B <= 256), weights and labels at four,
and an operation is one add or multiply on a scanned value.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def edge_scan_work(rows: int, d: int, num_bins: int) -> tuple[float, float]:
    """``(bytes, ops)`` of one histogram pass over ``rows`` scanned rows:
    read each row's d bins, its weight and its signed weight, write the
    (d, B) histogram and three sums; one add per feature into its bin,
    and the three sums W, V, T."""
    nbytes = rows * (d * 1 + 4 + 4) + (d * num_bins + 3) * 4
    ops = rows * (d + 4)
    return float(nbytes), float(ops)


def round_work(n_workers: int, rows: int, d: int) -> tuple[float, float]:
    """``(bytes, ops)`` one round requires: every worker scans ``rows``
    rows, each d bins, a weight and a label."""
    nbytes = n_workers * rows * (d * 1 + 4 + 4)
    ops = n_workers * rows * (d + 4)
    return float(nbytes), float(ops)


def least_time(nbytes: float, ops: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_ops = ops / peak["flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
