"""Metric arithmetic on plain Python numbers (no numpy, no jax: the parent
imports this too)."""

from __future__ import annotations

from typing import Optional, Sequence, Tuple


def iqr_share(values: Sequence[float]) -> float:
    """The spread the contract speaks of: distance between the first and the
    third quartile (``statistics.quantiles(values, n=4)``) over the median."""
    import statistics

    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def fenced_rate(
    fences: Sequence[Tuple[float, float]], t_open: float, seconds: float
) -> Optional[dict]:
    """Work per second between fences.

    ``fences`` are (time, cumulative work done and fenced at that time),
    in time order. The measured stretch runs from the first fence at or
    after ``t_open`` to the last fence at or before ``t_open + seconds``:
    all the work between two instants at which the device was known to be
    drained, over all the time between them. None when fewer than two
    fences fall inside the window."""
    inside = [(t, w) for t, w in fences if t_open <= t <= t_open + seconds]
    if len(inside) < 2:
        return None
    (t0, w0), (t1, w1) = inside[0], inside[-1]
    if t1 <= t0:
        return None
    return {
        "work": w1 - w0, "elapsed_s": t1 - t0, "rate": (w1 - w0) / (t1 - t0),
        "fences": len(inside),
    }
