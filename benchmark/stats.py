"""The arithmetic of the end-to-end metrics: percentiles with failures as
+inf, and the spread the bounds are set from."""

import math
import statistics


def percentile(values, q):
    """The q-th percentile (0-100) by the nearest-rank rule on the sorted
    values: the smallest value with at least q% of the samples at or below
    it. ``math.inf`` entries (failed, refused or unfinished requests) sort
    last, so enough of them make the percentile +inf and never drop out."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def median(values):
    return statistics.median(values)


def iqr_spread(values):
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)``: the driver's rule."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
