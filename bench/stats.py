"""Tails over all requests, and the spread statistic the bounds come from."""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank q-th percentile (0 < q <= 100) of all values.

    Nearest rank never interpolates, so an infinite latency (a request that
    failed or was refused) is read as such when the rank falls on it.
    """
    if not values:
        return math.nan
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def latencies_with_failures(
    answered: Sequence[float], n_failed: int, fail_value: float
) -> list[float]:
    """All requests' latencies: a failed request reads ``fail_value``,
    which the caller sets above every answered latency."""
    top = max(answered, default=0.0)
    return list(answered) + [max(fail_value, top + 1e-3)] * n_failed


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median (``statistics.quantiles``)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else math.inf


def spread_without_farthest(values: Sequence[float]) -> float:
    """Spread after leaving out the run farthest from the median."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return spread([v for i, v in enumerate(values) if i != far])
