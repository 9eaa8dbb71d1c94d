"""Seeded weights, one module per model family (``bench/weights/<family
module>.py``, named by a configuration's ``family_module``), each with
``program_params(key, m)``: the whole tree in the program's layout."""
from __future__ import annotations

import importlib

import numpy as np


def derive_seed(seed: int, purpose: int) -> int:
    """A 32-bit seed for one purpose (weights, traffic, sampling), from a
    run seed of any size."""
    ss = np.random.SeedSequence([int(seed) % 2**64, purpose])
    return int(ss.generate_state(1)[0])


def family(config: dict):
    """The weight builder a configuration names."""
    return importlib.import_module(f"bench.weights.{config['family_module']}")
