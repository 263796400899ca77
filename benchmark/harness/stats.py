"""The benchmark's own arithmetic: percentiles, rates over window edges,
the tail mean, the gap histogram and the spread rule.  Pure Python, no
numpy, so that the load generator's child can use it too."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default), on a copy sorted here."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * (q / 100.0)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def top_share_mean(values, share: float = 0.05) -> float:
    """Mean of the slowest ``share`` of the values (at least one)."""
    xs = sorted(values, reverse=True)
    if not xs:
        raise ValueError("tail mean of no values")
    n = max(1, int(math.ceil(len(xs) * share)))
    return float(sum(xs[:n]) / n)


def rate_in_window(stamps, t_open: float, t_close: float) -> float:
    """Events per second over the whole window: every stamp inside
    [t_open, t_close) counts, whichever request it belongs to."""
    if t_close <= t_open:
        raise ValueError("empty window")
    n = sum(1 for t in stamps if t_open <= t < t_close)
    return n / (t_close - t_open)


def gaps_in_window(token_stamps, t_open: float, t_close: float) -> list:
    """Gaps between consecutive tokens of one request whose later token
    arrived inside the window."""
    return [b - a for a, b in zip(token_stamps, token_stamps[1:])
            if t_open <= b < t_close]


def histogram(values, edges) -> list:
    """Counts per bucket: values < edges[0], then [edges[i-1], edges[i]),
    then >= edges[-1]."""
    counts = [0] * (len(edges) + 1)
    for v in values:
        i = 0
        while i < len(edges) and v >= edges[i]:
            i += 1
        counts[i] += 1
    return counts


def spread(values) -> float:
    """Distance between the first and third quartile, as a share of the
    median: the contract's spread (statistics.quantiles, n=4)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
