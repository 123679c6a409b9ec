"""The arithmetic of the end-to-end metrics, kept apart so that it is tested
on fixed inputs (tests/test_arithmetic.py)."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks of ALL the values given; no trimming, no chunks."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
