"""End-to-end arithmetic over the client timeline.

A timeline entry is one request as its client saw it, on one clock:
``due`` (when it was due to be sent), ``times`` (when each token arrived),
``budget`` (tokens asked for), ``admit`` (engine admission, same clock, or
None) and ``error`` (None, or why it ended early).  The window is
``[t0, t1)`` on that clock.
"""
from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float) -> float | None:
    """``q``-th percentile of every sample (linear interpolation); None
    for no samples.  A failed request enters as +inf."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return None
    rank = q / 100.0 * (v.size - 1)
    lo, frac = int(math.floor(rank)), rank - math.floor(rank)
    if frac == 0.0:
        return float(v[lo])
    if math.isinf(v[lo + 1]):
        return math.inf
    return float(v[lo] + (v[lo + 1] - v[lo]) * frac)


def failed(r: dict) -> bool:
    """Ended with an error, or delivered fewer tokens than asked for."""
    return r["error"] is not None or len(r["times"]) < r["budget"]


def due_in(timeline: list[dict], t0: float, t1: float) -> list[dict]:
    return [r for r in timeline if t0 <= r["due"] < t1]


def token_gaps(timeline: list[dict], t0: float, t1: float) -> list[float]:
    """Every gap between consecutive tokens of one request whose later
    token arrived inside the window."""
    out = []
    for r in timeline:
        ts = r["times"]
        out.extend(b - a for a, b in zip(ts, ts[1:]) if t0 <= b < t1)
    return out


def ttfts(timeline: list[dict], t0: float, t1: float) -> list[float]:
    """First token minus due time, for every request due in the window; a
    request that failed or never produced a token is +inf."""
    return [math.inf if failed(r) or not r["times"] else r["times"][0] - r["due"]
            for r in due_in(timeline, t0, t1)]


def tokens_in(timeline: list[dict], t0: float, t1: float) -> int:
    return sum(1 for r in timeline for t in r["times"] if t0 <= t < t1)


def queue_waits(timeline: list[dict], t0: float, t1: float) -> list[float]:
    """Due time to engine admission, for requests due in the window; one
    never admitted is +inf."""
    return [math.inf if r["admit"] is None else r["admit"] - r["due"]
            for r in due_in(timeline, t0, t1)]
