"""Traffic from a mix file and a seed.

A mix file (``bench/traffic/<mix>.json``) names its loop, a module
``bench/loops/<loop>.py`` that the harness finds by that name and that
says when requests are sent (``check``, ``gaps``, ``drive``), and its
lengths: ``prompt`` and ``output`` give a distribution each,
``lognormal`` with ``median``, ``sigma`` and the clip ``[min, max]``.

Every seed gets the same work: a deck of ``deck`` requests whose lengths
(and, for a loop with a schedule, gaps) are the distributions' quantiles
at (k + 1/2) / deck, so no deck is lighter or heavier than another, put
in one order drawn from the mix's ``order_seed``.  The run seed draws only
the token ids (uniform over the vocabulary), so every run's window holds
the same lengths at the same times.  A run cycles through the deck as far
as its window reaches.
"""
from __future__ import annotations

import importlib
from statistics import NormalDist

import numpy as np


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified lengths of ``spec`` (unshuffled)."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.asarray([NormalDist().inv_cdf((k + 0.5) / n) for k in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def loop(name: str):
    """The loop module ``bench/loops/<name>.py``."""
    try:
        return importlib.import_module(f"bench.loops.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"bench.loops.{name}":
            raise
        raise ValueError(f"unknown loop {name!r}") from e


class Traffic:
    """One run's requests: ``item(i)`` is the i-th request's prompt ids and
    output budget; for a loop with a schedule ``due(i)`` is its due time in
    seconds from the window's start."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab, self.loop = mix, vocab, loop(mix["loop"])
        n = mix["deck"]
        order = np.random.default_rng(mix["order_seed"])
        self._prompt = order.permutation(lengths(mix["prompt"], n))
        self._output = order.permutation(lengths(mix["output"], n))
        g = self.loop.gaps(mix, n)
        self._due = None if g is None else np.cumsum(order.permutation(g))
        self._ids = np.random.default_rng(seed)
        self._drawn: list[list[int]] = []

    def item(self, i: int) -> tuple[list[int], int]:
        """Request ``i`` (any i >= 0: the deck repeats).  Ids are drawn in
        request order, so they depend only on the seed and the index."""
        n = len(self._prompt)
        while len(self._drawn) <= i:
            plen = int(self._prompt[len(self._drawn) % n])
            self._drawn.append(self._ids.integers(0, self.vocab, plen).tolist())
        return self._drawn[i], int(self._output[i % n])

    def due(self, i: int) -> float:
        """Due time of request ``i`` (cumulative gaps, the deck repeated)."""
        if self._due is None:
            raise TypeError(f"a {self.mix['loop']} loop has no schedule")
        laps, k = divmod(i, len(self._due))
        return float(laps * self._due[-1] + self._due[k])

    def prefill_lengths(self) -> list[int]:
        """Every prompt length the mix can draw."""
        return sorted(set(self._prompt.tolist()))


def buckets(lengths_: list[int], bucket: int, max_len: int) -> list[int]:
    """The padded prefill lengths those prompts fall into (the engine pads
    a prompt up to a multiple of ``bucket``, at most ``max_len``)."""
    return sorted({min(max_len, -(-n // bucket) * bucket) for n in lengths_})


def check(mix: dict) -> None:
    """Reject a mix whose loop is unknown or mis-set, or whose longest
    prompt plus longest output does not fit a slot."""
    s = mix["serving"]
    need = mix["prompt"]["max"] + mix["output"]["max"]
    if need > s["max_len"]:
        raise ValueError(f"prompt max + output max = {need} > max_len "
                         f"{s['max_len']}")
    loop(mix["loop"]).check(mix)
