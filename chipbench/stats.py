"""Percentiles, windows and spreads, kept with the benchmark so that no
change to the program moves the arithmetic of its yardstick."""
from __future__ import annotations

import math
import statistics
from typing import Iterable, Sequence, Tuple


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100).  Failures are passed in
    as ``math.inf`` and rank above every finite value, so a tail with
    failures beyond it is infinite.  NaN for no values."""
    xs = sorted(values)
    if not xs:
        return math.nan
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


def window(slice_ends: Sequence[float], t_from: float,
           t_to: float) -> Tuple[float, float]:
    """The measured window: from the first slice end at or after
    ``t_from`` to the last slice end at or before ``t_to``, so that no
    slice is counted in part.  Raises when fewer than two slices end in
    that span."""
    ends = sorted(t for t in slice_ends if t_from <= t <= t_to)
    if len(ends) < 2:
        raise ValueError(f"{len(ends)} slice(s) ended between {t_from:.3f} "
                         f"and {t_to:.3f} s; a window needs two")
    return ends[0], ends[-1]


def window_rate(slices: Iterable[Tuple[float, int]], t_open: float,
                t_close: float) -> float:
    """Tokens of the slices that ended in (t_open, t_close], per second of
    the window.  ``slices`` are (end time, tokens delivered) pairs."""
    if t_close <= t_open:
        raise ValueError("empty window")
    n = sum(tok for t, tok in slices if t_open < t <= t_close)
    return n / (t_close - t_open)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile, as a share of the
    median (``statistics.quantiles(values, n=4)``)."""
    q1, med, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / med

