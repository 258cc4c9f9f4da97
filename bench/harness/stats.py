"""Metric arithmetic over every sample of the window.

Tails are order statistics of all samples (linear interpolation between
neighbours, numpy's default), never bucketed histograms; rates are work
over the whole window.
"""
from __future__ import annotations

import math
from typing import Sequence


def percentile(xs: Sequence[float], p: float) -> float:
    """p in [0, 100]; linear interpolation between order statistics."""
    if not xs:
        raise ValueError("percentile of no samples")
    s = sorted(xs)
    pos = (len(s) - 1) * p / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def rate(work: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("rate over an empty window")
    return work / seconds

