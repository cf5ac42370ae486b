"""End-to-end arithmetic over the load loop's records (host clock, seconds)."""
from __future__ import annotations

import math
from typing import Iterable, List, Sequence


def pct(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile: the smallest value with at least
    ``q`` percent of the sample at or below it.  ``inf`` (a request that
    never answered) sorts last."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


def due_in(recs: Iterable, w0: float, w1: float) -> List:
    return [r for r in recs if w0 <= r.due < w1]


def ttfts(recs: Iterable, w0: float, w1: float) -> List[float]:
    """Time to first token of every request due in ``[w0, w1)``, from its
    due time; a failed or unanswered request counts as ``inf``."""
    out = []
    for r in due_in(recs, w0, w1):
        if r.failed or r.first_t is None:
            out.append(math.inf)
        else:
            out.append(r.first_t - r.due)
    return out


def gaps(recs: Iterable, w0: float, w1: float) -> List[float]:
    """Every gap between consecutive tokens of one request whose later
    token came inside ``(w0, w1]``."""
    out = []
    for r in recs:
        s = r.stamps
        out.extend(b - a for a, b in zip(s, s[1:]) if w0 < b <= w1)
    return out


def tokens_in(recs: Iterable, w0: float, w1: float) -> int:
    return sum(1 for r in recs for t in r.stamps if w0 < t <= w1)


def in_window(spans: Iterable, name: str, w0: float,
              w1: float) -> List[float]:
    """Durations of the spans called ``name`` that start in the window."""
    return [b - a for n, a, b in spans if n == name and w0 <= a < w1]

