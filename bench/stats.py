"""Percentiles, windows and rates, shared by every driver and reader."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) of all values, by linear interpolation
    between closest ranks (numpy's default). No chunking, no medians of
    medians. Empty input is an error."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttfts(requests, settled: float) -> list:
    """First-token time minus due time, for every request given (each a
    dict with 'due' and 'token_times'). A request that still has no
    token when the harness stopped waiting, at `settled`, counts as
    settled minus due, the least it waited."""
    return [(r["token_times"][0] if r["token_times"] else settled)
            - r["due"] for r in requests]


def gaps(requests, open_t: float, close: float) -> list:
    """Every gap between consecutive output tokens of every request, where
    the later token came inside (open_t, close]."""
    out = []
    for r in requests:
        t = r["token_times"]
        out += [b - a for a, b in zip(t, t[1:]) if open_t < b <= close]
    return out


def tokens_in(requests, open_t: float, close: float) -> int:
    """Output tokens whose time lies inside (open_t, close]."""
    return sum(1 for r in requests for t in r["token_times"]
               if open_t < t <= close)
