"""The arithmetic of the end-to-end metrics, over raw client samples.

A sample is one completed, correct statement: (group, class, submit
time, completion time), times in seconds on the client's monotonic
clock. A failed or wrong statement is no sample: it misses every
latency and counts in ``failed``.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# a tail is reported only where ten samples lie beyond it
SAMPLES_BEYOND_A_TAIL = 10


def quantile(values: Sequence[float], q: float) -> float:
    """The q-quantile by linear interpolation between order statistics
    (the 'inclusive' method: the smallest sample is q=0, the largest
    q=1)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("quantile of no samples")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def geomean_of_group_medians(
        latencies_by_group: Dict[str, List[float]]) -> float:
    """Geometric mean, over the groups (a cell's statement ids), of each
    group's median latency. TPC-H's power metric is a geometric mean
    for the same reason: a deck of a 0.6 s and a 30 s statement has no
    one typical latency, and a plain median over it flips between the
    two."""
    medians = [statistics.median(v)
               for _g, v in sorted(latencies_by_group.items()) if v]
    if not medians:
        raise ValueError("no group has a sample")
    return math.exp(sum(math.log(m) for m in medians) / len(medians))


def min_tail_samples(q: float) -> int:
    """Samples a q-quantile needs: 100 for the 90th percentile, 200 for
    the 95th."""
    return math.ceil(round(SAMPLES_BEYOND_A_TAIL / (1.0 - q), 6))


def tail(latencies: Iterable[float], q: float
         ) -> Tuple[Optional[float], int]:
    """(q-quantile, sample count); the quantile is None where fewer
    than ten samples would lie beyond it."""
    xs = list(latencies)
    if len(xs) < min_tail_samples(q):
        return None, len(xs)
    return quantile(xs, q), len(xs)


def throughput(completions: Sequence[float], seconds: float) -> float:
    """Correct statements completed inside the window, per second of
    it. ``completions`` are seconds since the window's start. A
    statement in flight when the window closes is awaited for its
    latency, but is no work of the window: the clients that are done
    have stopped by then, and a rate that ran on to the last completion
    would swing with how long the last batch statement had left."""
    if seconds <= 0:
        raise ValueError("no window")
    return sum(1 for t in completions if t <= seconds) / seconds


def spread(values: Sequence[float]) -> float:
    """Interquartile range over the median, by statistics.quantiles
    (the driver's definition of a metric's run-to-run spread)."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
