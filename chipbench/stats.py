"""Arithmetic of the window: whole-window means, spreads."""
from __future__ import annotations

import statistics
from typing import Optional, Sequence


def per_op(total_s: float, n: int) -> Optional[float]:
    """Seconds per operation over the whole window: all the time the window
    spent in the operation, divided by how many it completed."""
    return total_s / n if n > 0 else None


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartiles as a share of the
    median (``statistics.quantiles(values, n=4)``)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def megabytes(nbytes: int) -> float:
    return nbytes / 1e6
