"""Order statistics used by the metrics.

``percentile`` is the exact nearest-rank quantile of
``benchmarks/common.percentile`` and ``repro.obs.metrics.Histogram``,
copied so that the benchmark's arithmetic cannot change with the program.
``spread`` is the interquartile distance over the median, with the
quartiles of ``statistics.quantiles(values, n=4)``.
"""

from __future__ import annotations

import statistics


def percentile(xs, p: float) -> float:
    """Nearest-rank ``p``-th percentile of a non-empty sequence."""
    xs = sorted(xs)
    if not xs:
        raise ValueError("percentile of an empty sequence")
    idx = min(len(xs) - 1, max(0, int(round(p / 100 * (len(xs) - 1)))))
    return float(xs[idx])


def median(xs) -> float:
    return float(statistics.median(xs))


def spread(xs) -> float:
    """(Q3 - Q1) / median."""
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)
