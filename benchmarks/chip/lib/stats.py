"""The benchmark's statistics, kept here so that every PR computes the
same number the same way."""

import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear interpolation between closest ranks (numpy's default);
    None for no values."""
    data = sorted(values)
    if not data:
        return None
    at = (len(data) - 1) * q / 100.0
    lo = int(at)
    hi = min(lo + 1, len(data) - 1)
    return data[lo] + (data[hi] - data[lo]) * (at - lo)


def spread(values: Sequence[float]) -> Optional[float]:
    """Inter-quartile distance as a share of the median, the way the
    driver reckons it (``statistics.quantiles(values, n=4)``)."""
    if len(values) < 2:
        return None
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med if med else None


def cv(values: Sequence[float]) -> Optional[float]:
    if len(values) < 2:
        return None
    mean = statistics.fmean(values)
    return statistics.pstdev(values) / mean if mean else None
