"""The arithmetic between samples and the numbers reported: percentiles,
rates and the spread the bounds are set from.  Kept apart so that
``tests/`` can hold it to hand-worked samples."""

import math
import statistics


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (NumPy's default ``linear`` method)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0 <= q <= 100:
        raise ValueError("percentile %r outside 0..100" % (q,))
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def supports_percentile(n, q):
    """Whether ``n`` samples leave at least ten beyond the ``q``-th
    percentile (the choosing-metrics rule)."""
    return n * (100.0 - q) / 100.0 >= 10


def rate(work, seconds):
    """All the work over all the time, never a best or a median."""
    if seconds <= 0:
        raise ValueError("rate over %r seconds" % (seconds,))
    return work / seconds


def spread(values):
    """Interquartile distance as a share of the median, by
    ``statistics.quantiles(values, n=4)``: what the bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
