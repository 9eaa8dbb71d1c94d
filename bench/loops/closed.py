"""Closed loop: ``clients`` clients, each sending its next request when its
previous one has finished.  A request is due when it is sent."""
from __future__ import annotations

import asyncio
import itertools


def check(mix: dict) -> None:
    if not (isinstance(mix.get("clients"), int) and mix["clients"] >= 1):
        raise ValueError("a closed loop needs clients >= 1")


def gaps(mix: dict, n: int) -> None:
    """No schedule: requests follow their predecessors."""
    return None


async def drive(traffic, clock, t0: float, t1: float, send) -> None:
    """Until ``t1``, each client sends request ``i`` (``send(i, due)``
    returns its task) and awaits it before the next."""
    index = itertools.count()

    async def client():
        while clock() < t1:
            await send(next(index), clock())

    await asyncio.gather(*(client() for _ in range(traffic.mix["clients"])))
