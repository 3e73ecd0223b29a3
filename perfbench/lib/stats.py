"""Percentiles, spreads and open-loop arithmetic (stdlib only)."""

from __future__ import annotations

import math
import statistics


def percentile(values, q, missing=0):
    """The q-th percentile (0..100) by nearest rank over ``values`` plus
    ``missing`` samples placed above every value.  Returns None when the
    rank falls among the missing ones or there is no sample."""
    n = len(values) + missing
    if n == 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * n))
    if rank > len(values):
        return None
    return sorted(values)[rank - 1]


def median(values):
    return statistics.median(values) if values else None


def iqr_share(values):
    """Distance between the first and third quartile over the median, as
    the bound rule defines a spread."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def token_gaps(part_times, part_sizes):
    """Gaps between successive output tokens as a client receives them:
    tokens that arrive in one streamed part share that part's gap equally.
    Returns [(arrival time of the part, gap per token)] per token, first
    part (the first token's wait is TTFT, not a gap) excluded."""
    gaps = []
    for i in range(1, len(part_times)):
        n = part_sizes[i]
        if n > 0:
            g = (part_times[i] - part_times[i - 1]) / n
            gaps.extend([(part_times[i], g)] * n)
    return gaps
