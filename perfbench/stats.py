"""Metric arithmetic of the benchmark: percentiles, spreads, and token
counts credited to a window by when the tokens were produced.

Everything here is plain Python on lists of floats, so that the tests
can check it against hand-computed values.
"""

from __future__ import annotations

import math
import statistics


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics (numpy's default rule), on a copy of ``values``."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def samples_beyond(n, q):
    """How many of ``n`` samples lie beyond the q-th percentile."""
    return n - math.ceil(n * q / 100.0)


def spread(values):
    """Distance between the first and the third quartile as a share of
    the median, quartiles as ``statistics.quantiles(values, n=4)`` gives
    them (the contract's definition; numpy's lie closer together)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))


def trimmed(values):
    """``values`` without the one farthest from their median."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def overlap_share(start, end, lo, hi):
    """Share of the interval [start, end] that lies inside [lo, hi]. A
    zero-length interval counts whole if its instant is inside."""
    if end <= start:
        return 1.0 if lo <= start < hi else 0.0
    return max(0.0, min(end, hi) - max(start, lo)) / (end - start)


def tokens_in_window(prefills, token_times, lo, hi):
    """Tokens produced inside [lo, hi).

    ``prefills`` is a list of (start, end, n_tokens): a prompt's tokens
    are credited evenly over the time from its submission to its first
    output token, because the benchmark cannot see single chunks from
    its side. ``token_times`` is every output token's arrival time. A
    request that straddles an edge of the window is credited with the
    part that fell inside, whether or not it finished there.
    """
    total = sum(n * overlap_share(s, e, lo, hi) for s, e, n in prefills)
    return total + sum(1 for t in token_times if lo <= t < hi)


def gaps(times):
    """Differences between successive entries of ``times``."""
    return [b - a for a, b in zip(times, times[1:])]


def median_step_s(t0, ends):
    """Median time of one step, from the window's start and the time
    each step ended."""
    times = [t0] + list(ends)
    return statistics.median(b - a for a, b in zip(times, times[1:]))
