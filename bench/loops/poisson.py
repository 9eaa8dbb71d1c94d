"""Open loop: requests due at exponential gaps of mean ``1 / rate`` seconds,
whatever the server does."""
from __future__ import annotations

import asyncio
import math

import numpy as np


def check(mix: dict) -> None:
    rate = mix.get("rate")
    if not (isinstance(rate, (int, float)) and math.isfinite(rate)
            and rate > 0):
        raise ValueError("a poisson loop needs a finite rate > 0")


def gaps(mix: dict, n: int) -> np.ndarray:
    """``n`` stratified inter-arrival gaps: the exponential's quantiles at
    (k + 1/2) / n."""
    return -np.log1p(-(np.arange(n) + 0.5) / n) / mix["rate"]


async def drive(traffic, clock, t0: float, t1: float, send) -> None:
    """Send request ``i`` at its due time, for every due time in the
    window; the requests run on without being awaited here."""
    i = 0
    while (due := t0 + traffic.due(i)) < t1:
        await asyncio.sleep(max(0.0, due - clock()))
        send(i, due)
        i += 1
