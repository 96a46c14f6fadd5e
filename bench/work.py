"""The work of applying a rotation sequence, counted from shapes alone.

One application of ``k`` waves of ``n - 1`` planar rotations to an
``m x n`` target is the algorithm's work, whichever backend runs it:

* flops: each plane update costs 6 flops per row (4 multiplies and 2
  adds), so ``6 * m * (n - 1) * k``;
* bytes: the target read once and written once, and the cosines and
  sines read once: ``2 * m * n * itemsize + 2 * (n - 1) * k * itemsize``.

A backend that does more (the GEMM family's accumulated factors) or
moves more (several passes over the target) is judged against this
least work, so its roofline share says how far it is from the
algorithm's bound, not from its own.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def flops(m: int, n: int, k: int) -> float:
    return 6.0 * m * (n - 1) * k


def bytes_moved(m: int, n: int, k: int, itemsize: int = 4) -> float:
    return float(2 * m * n * itemsize + 2 * (n - 1) * k * itemsize)


def load_peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; an unknown device raises."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {path}")
    return table[device_kind]


def least_seconds(fl: float, by: float, peaks: dict) -> tuple[float, str]:
    """``(seconds, bound)``: the least time the chip could take for
    ``fl`` flops and ``by`` bytes, and which of the two bounds it."""
    t_flops = fl / peaks["flops_per_s"]
    t_bytes = by / peaks["bytes_per_s"]
    if t_bytes >= t_flops:
        return t_bytes, "memory"
    return t_flops, "compute"
