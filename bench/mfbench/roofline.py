"""The chip's peaks and the work of the reference algorithm.

The work is counted from shapes and from the reference algorithm, never
from the compiled program, so a change to the program cannot move its
own yardstick.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).with_name("peaks.json")

#: Higham 2005: the 1-norm up to which Pade-13 needs no scaling.
THETA13 = 5.371920351148152


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of one chip; an unknown chip is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to {PEAKS.name} with its source")
    return table[device_kind]


def expm_squarings(norm1: float) -> int:
    """Squarings of Pade-13 scaling and squaring: max(0, ceil(log2(
    ||A||_1 / theta13)))."""
    if norm1 <= THETA13:
        return 0
    return int(math.ceil(math.log2(norm1 / THETA13)))


def squaring_flops(n: int) -> float:
    """Operations of one dense n x n squaring: n^2 dot products of n."""
    return 2.0 * n ** 3


def squaring_bytes(n: int, itemsize: int = 4) -> float:
    """Bytes one squaring must move at the least: read A twice, write A^2."""
    return 3.0 * n * n * itemsize


def roofline_share(squarings: int, n: int, kernel_s: float, kind: str,
                   itemsize: int = 4):
    """(share in %, "compute" or "memory") of ``squarings`` n x n
    squarings that took ``kernel_s`` seconds of kernel time, or None
    where no kernel time was found."""
    if kernel_s <= 0 or squarings <= 0:
        return None
    p = peaks(kind)
    t_flops = squarings * squaring_flops(n) / p["flops_per_s"]
    t_bytes = squarings * squaring_bytes(n, itemsize) / p["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / kernel_s, bound


def norm1(a) -> float:
    return float(np.abs(np.asarray(a, np.float64)).sum(axis=0).max())
