"""Statistics over all requests and the whole window.

Latency percentiles are taken over every request due in the window,
including those decided late and those never decided (their latency
runs to the end of the loop), and a rate is the count over the window's
full length: no statistic is a median of chunks or of quanta.
"""
from __future__ import annotations

import numpy as np


def latencies(due: np.ndarray, decided: np.ndarray, lo: float, hi: float,
              end: float) -> np.ndarray:
    """Seconds from due to decision for every request due in [lo, hi)."""
    sel = (due >= lo) & (due < hi)
    got = np.where(np.isnan(decided[sel]), end, decided[sel])
    return got - due[sel]


def percentile(x: np.ndarray, q: float) -> float:
    """The ``q``-th percentile (nearest rank, so it is a real sample)."""
    if len(x) == 0:
        return float("nan")
    s = np.sort(x)
    k = max(0, int(np.ceil(q / 100.0 * len(s))) - 1)
    return float(s[k])


def rate(decided: np.ndarray, lo: float, hi: float) -> float:
    """Decisions made inside [lo, hi), per second of it."""
    return float(np.sum((decided >= lo) & (decided < hi))) / (hi - lo)


def spread(values) -> float:
    """Interquartile range over the median (``statistics.quantiles``)."""
    from statistics import median, quantiles
    q = quantiles(values, n=4)
    return (q[2] - q[0]) / median(values)
