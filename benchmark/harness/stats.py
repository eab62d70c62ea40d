"""The arithmetic of the end-to-end metrics, kept here so that no later PR
can change what a name means."""

from __future__ import annotations

import math
import statistics

def percentile(values, q: float) -> float:
    """q in [0, 100], linear interpolation between order statistics."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def step_ms(step_seconds, q: float) -> float:
    """The q-th percentile, in milliseconds, of the window's per-step
    seconds: one value for every step the loop ran in the window, from the
    loop's own per-step record (``t_fetch + t_comp``), none merged, none
    left out. A stall inside one step stands in the tail at its full
    length."""
    return 1e3 * percentile(step_seconds, q)


def examples_per_s(steps: int, examples_per_step: int,
                   window_s: float) -> float:
    """Distinct training examples consumed over the whole window; the
    redundant copies a coded worker computes are not counted."""
    if window_s <= 0 or steps <= 0:
        raise ValueError("empty window")
    return steps * examples_per_step / window_s


def spread(values) -> float:
    """Interquartile distance over the median, as the contract reads it."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
