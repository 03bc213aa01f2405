"""Window accounting on the host clock: pure functions of timestamps.

Every function takes plain numbers, so the tests drive them with a made-up
clock. Percentiles are nearest-rank (the smallest value with at least
``q`` percent of the sample at or below it), so a tail is always a time
some request really saw.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank ``q``-th percentile; None for an empty sample."""
    vals = sorted(values)
    if not vals:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return float(vals[rank - 1])


def ttft_due(requests: Iterable[Dict], t_open: float,
             t_close: float) -> List[float]:
    """Time to first token from the DUE time, for every request due in
    ``[t_open, t_close)``. A request with no first token by ``t_close``
    counts at its age then (censored), so a stall cannot hide it.

    Each request is ``{"due": t, "first": t_first_token or None}``.
    """
    out = []
    for r in requests:
        if not t_open <= r["due"] < t_close:
            continue
        first = r.get("first")
        if first is None or first > t_close:
            out.append(t_close - r["due"])
        else:
            out.append(first - r["due"])
    return out


def inter_token_gaps(token_times: Iterable[Sequence[float]], t_open: float,
                     t_close: float) -> List[float]:
    """Gaps between consecutive tokens of one request whose later token
    falls in ``(t_open, t_close]``."""
    out = []
    for times in token_times:
        for a, b in zip(times, times[1:]):
            if t_open < b <= t_close:
                out.append(b - a)
    return out


def tokens_in_window(token_times: Iterable[Sequence[float]], t_open: float,
                     t_close: float) -> int:
    """Tokens emitted (first tokens included) in ``(t_open, t_close]``."""
    return sum(1 for times in token_times for t in times
               if t_open < t <= t_close)


def rate(count: float, t_open: float, t_close: float) -> float:
    """A count over the whole window's seconds."""
    return count / (t_close - t_open)
