"""Percentile arithmetic, kept with the benchmark (checked by
`selfcheck.py`)."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default),
    q in [0, 100], on a copy of `values`."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)
