"""The statistics of a window: median, tail, throughput.

A timing is reported as its median and the highest percentile that has
at least ten samples beyond it (choosing-metrics §1); a percentile asked
for by name that the window cannot support is an error, never a smaller
percentile under the same name.
"""

from __future__ import annotations

import math

LADDER = (50.0, 90.0, 95.0, 99.0, 99.9)
BEYOND = 10


def percentile(samples, p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not samples:
        raise ValueError("percentile of no samples")
    s = sorted(samples)
    rank = (len(s) - 1) * p / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (rank - lo)


def median(samples) -> float:
    return percentile(samples, 50.0)


def samples_beyond(n: int, p: float) -> float:
    """How many of n samples lie beyond the p-th percentile."""
    return n * (100.0 - p) / 100.0


def supported(n: int, p: float) -> bool:
    # 100 - 99.9 is not 0.1 in binary; ten means ten.
    return samples_beyond(n, p) >= BEYOND - 1e-6


def highest_supported(n: int) -> float | None:
    """The highest percentile of the ladder with ten samples beyond it."""
    ok = [p for p in LADDER if supported(n, p)]
    return ok[-1] if ok else None


def tail(samples, p: float) -> float:
    """The p-th percentile, refused when fewer than ten samples lie
    beyond it."""
    if not supported(len(samples), p):
        raise ValueError(
            f"p{p:g} needs {BEYOND} samples beyond it; {len(samples)} "
            f"samples leave {samples_beyond(len(samples), p):.1f}")
    return percentile(samples, p)


def per_second(count: float, window_s: float) -> float:
    if window_s <= 0:
        raise ValueError(f"window of {window_s} s")
    return count / window_s


def spread(values) -> float:
    """Distance between the quartiles over the median: what the driver
    calls a metric's spread over a set of runs."""
    return (percentile(values, 75.0) - percentile(values, 25.0)) / median(
        values)
