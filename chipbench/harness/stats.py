"""The arithmetic from timestamps to metrics."""
import math


def percentile(values, q):
    """The ``q``-th percentile (0..100) with linear interpolation between
    order statistics (numpy's default rule); None for no values."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50)


def mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None


def token_gaps(stamps):
    """Gaps between consecutive arrival times of one request's tokens."""
    return [b - a for a, b in zip(stamps, stamps[1:])]


def in_window(stamps, t0, t1):
    return sum(1 for t in stamps if t0 <= t < t1)
