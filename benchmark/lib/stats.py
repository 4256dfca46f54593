"""Percentile arithmetic on raw samples (no histogram buckets)."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics (numpy's default, "linear"). Raises on no samples: a
    tail of nothing is not 0."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * (q / 100.0)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def mean(values) -> float:
    xs = [float(v) for v in values]
    if not xs:
        raise ValueError("mean of no samples")
    return sum(xs) / len(xs)


def edge_rate(arrivals, t0: float, t1: float):
    """Tokens per second of one request's stream between two of its own
    arrivals.

    ``arrivals`` are the stream's token arrivals (time, count) in order. A
    decode dispatch's tokens reach the client together, so a count over a
    window cut at arbitrary instants would move by a whole dispatch with
    the window's phase. The stream's window runs from its first arrival at
    or after ``t0`` to its first arrival at or after ``t1`` and holds every
    arrival after the first of those two up to and with the second: the
    tokens the system produced for it between the two edges. Returns
    (tokens per second, tokens, first edge, second edge); raises when the
    arrivals do not reach past ``t1``."""
    a = next((i for i, (t, _n) in enumerate(arrivals) if t >= t0), None)
    b = next((i for i, (t, _n) in enumerate(arrivals) if t >= t1), None)
    if a is None or b is None or b <= a:
        raise ValueError("no arrival on both sides of the window")
    tokens = sum(n for _t, n in arrivals[a + 1:b + 1])
    e0, e1 = arrivals[a][0], arrivals[b][0]
    return tokens / (e1 - e0), tokens, e0, e1
