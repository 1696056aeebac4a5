"""The percentile arithmetic, kept with the benchmark."""
from __future__ import annotations


def percentile(values, q: float) -> float:
    """Linear interpolation between the closest ranks (numpy's default
    definition): the q-th percentile of ``values``."""
    v = sorted(float(x) for x in values)
    if not v:
        raise ValueError("percentile of no values")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)

