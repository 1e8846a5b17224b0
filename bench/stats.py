"""Whole-run arithmetic for the end-to-end metrics.

A window is the list of runs the closed loop completed, each a
``(start, end, edges)`` record on the host's monotonic clock.  Rates and
percentiles are taken over whole runs only, and the span a rate divides
by runs from the window's start to the end of the last run it counts, so
a run that stalls counts with all of its time.
"""

from __future__ import annotations

import math


def rate(window_start: float, runs) -> float:
    """Work per second over the window: all edges of the completed runs
    over the time from the window's start to the last completion."""
    if not runs:
        raise ValueError("no completed run in the window")
    end = max(r[1] for r in runs)
    return sum(r[2] for r in runs) / (end - window_start)


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100), interpolated linearly between order
    statistics as numpy's default does."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)

