# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Shared Pallas/TPU helpers for the kernel suite."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` picks the mode from the platform: Mosaic-compiled on the
    TPU, the Pallas interpreter everywhere else (the CPU test backend).
    Called at trace time, so a jitted kernel never asks again."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
