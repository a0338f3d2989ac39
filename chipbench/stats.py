"""Client-side latency arithmetic over a run's record.

``nearest_rank`` is the percentile of ``repro.core.metrics.latency_stats``
(the ceil(q n)-th order statistic), copied so that the yardstick does not
move with the program. Every time here is the client's: when a request was
due, and when each of its tokens was back on the host.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def nearest_rank(values: Iterable[float], q: float) -> Optional[float]:
    """The ceil(q n)-th smallest of ``values`` (1-based), or None if empty."""
    s = sorted(values)
    if not s:
        return None
    return s[max(math.ceil(q * len(s)) - 1, 0)]


def ttfts(reqs: Sequence, t_open: float, t_close: float) -> List[float]:
    """First-token time of every request due in [t_open, t_close), from its
    due time. One with no first token by t_close counts at its wait so far
    (censored), so a stalled server cannot look fast by finishing little."""
    out = []
    for r in reqs:
        if r.due_at is None or not t_open <= r.due_at < t_close:
            continue
        first = r.token_times[0] if r.token_times else None
        if first is None or first >= t_close:
            out.append(t_close - r.due_at)
        else:
            out.append(first - r.due_at)
    return out


def itls(reqs: Sequence, t_open: float, t_close: float) -> List[float]:
    """Every gap between consecutive tokens of one request with both tokens
    in [t_open, t_close), pooled over requests (not per-request means)."""
    out = []
    for r in reqs:
        ts = [t for t in r.token_times if t_open <= t < t_close]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in(reqs: Sequence, t_open: float, t_close: float) -> int:
    """Output tokens that reached the client inside [t_open, t_close)."""
    return sum(t_open <= t < t_close for r in reqs for t in r.token_times)
