"""Algorithmic work of one call, from shapes alone.

The counts are deliberately independent of the implementation: padding d
to 128 lanes, K to a tile, or a one-hot update matmul are choices of one
kernel and are not counted.  A kernel that drops such overhead reads the
same work, so its share rises by what it saved and never passes 100%.
"""

from __future__ import annotations

F32 = 4


def lloyd_step(n: int, k: int, d: int) -> tuple[float, float]:
    """(FLOP, bytes) of one Lloyd step: the distance cross term
    2·N·K·d, reading X and C once and writing one label per row."""
    return 2.0 * n * k * d, float(F32 * (n * d + k * d + n))


def assign_call(rows: int, k: int, d: int) -> tuple[float, float]:
    """(FLOP, bytes) of assigning ``rows`` rows to K centroids: the
    distance cross term, and reading the rows once."""
    return 2.0 * rows * k * d, float(F32 * rows * d)


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def bound(flops: float, nbytes: float, peaks: dict) -> str:
    """Which of the two bounds sets `least_time`."""
    return "compute" if flops / peaks["flops_per_s"] >= \
        nbytes / peaks["hbm_bytes_per_s"] else "memory"
