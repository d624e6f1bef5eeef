"""Percentiles, rates and the interval arithmetic the readers share."""

from __future__ import annotations

import math
import re
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) with linear interpolation between
    the two nearest ranks (numpy's default); None for no values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def name_percentile(name: str, default: float = 50.0) -> float:
    """The NN of a metric name's ``pNN`` part, after a dot or between
    underscores (``prefill_ms.p90.prefill`` and ``ttft_p90_ms`` read 90),
    or ``default`` where it has none."""
    m = re.search(r"(?:\.|_)p(\d+)(?=\.|_|$)", name)
    return float(m.group(1)) if m else default


def ttfts(due: Sequence[float], first: Sequence[Optional[float]],
          close: float) -> List[float]:
    """Time to first token of every request due before ``close``.  A
    request with no first token by then (``None``, or one that came
    after the close) counts with the time it has waited so far, so a
    stall at the end of the window shows in the tail."""
    out = []
    for d, f in zip(due, first):
        if d >= close:
            continue
        out.append((f if f is not None and f <= close else close) - d)
    return out


def window_ttfts(recs, window) -> List[float]:
    """:func:`ttfts` of the client's records due in the window
    (``open``, ``close``)."""
    due = [r for r in recs if window["open"] <= r.due < window["close"]]
    return ttfts([r.due for r in due],
                 [r.times[0] if r.times else None for r in due],
                 window["close"])


def gaps_in_window(times: Sequence[float], open_: float,
                   close: float) -> List[float]:
    """Gaps between consecutive token times of one request whose later
    token was returned inside ``[open_, close)``."""
    return [b - a for a, b in zip(times, times[1:]) if open_ <= b < close]


def count_in_window(times: Iterable[float], open_: float,
                    close: float) -> int:
    return sum(1 for t in times if open_ <= t < close)


def union_length(intervals: Iterable[Tuple[float, float]],
                 lo: float, hi: float) -> float:
    """Length of the union of ``[start, end)`` intervals, clipped to
    ``[lo, hi)``."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: Iterable[Tuple[float, float]], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi)`` that no interval covers."""
    out = []
    t = lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]
