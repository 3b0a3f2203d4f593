"""Small statistics shared by the metric readers."""
from __future__ import annotations

import math
from typing import List, Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The ``q`` quantile (0 < q <= 1) by nearest rank: the smallest
    value with at least ``q`` of the sample at or below it.  Infinite
    values (failed requests) sort last."""
    if not values:
        raise ValueError("quantile of an empty sample")
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def ttfts(run) -> List[float]:
    """Time to first token of every request counted in the run, from its
    due time; a request that never got one counts as infinite."""
    out = []
    for it in run.counted:
        st = run.wall.stamps.get(it.rid, [])
        out.append(st[0] - it.due_s if st else math.inf)
    return out


def token_gaps(run) -> List[float]:
    """Seconds between consecutive tokens of every request counted."""
    gaps = []
    for it in run.counted:
        st = run.wall.stamps.get(it.rid, [])
        gaps.extend(b - a for a, b in zip(st, st[1:]))
    return gaps
