"""The arithmetic of the end-to-end metrics: percentiles by linear
interpolation between the two closest ranks, and how many samples lie
beyond one (a percentile is reported only with ten or more beyond it:
choosing-metrics section 1); and the same percentiles of each third of
a run's samples, so that a drift inside the window shows in the facts."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) of ``values``; raises on none."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0 + 1e-9))


def thirds(values) -> list[dict]:
    """Count, median and 90th percentile of each third of ``values`` in
    the order they were taken (one closed-loop caller: thirds of the
    samples are thirds of the window to within the drift they show).
    The first thirds take the remainder; a third with no sample is left
    out."""
    xs = list(values)
    cuts = [(k * len(xs) + 2) // 3 for k in range(4)]
    return [
        {"n": hi - lo, "p50": percentile(xs[lo:hi], 50),
         "p90": percentile(xs[lo:hi], 90)}
        for lo, hi in zip(cuts, cuts[1:]) if hi > lo
    ]
