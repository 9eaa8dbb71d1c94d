"""The chip peaks that counts are divided by (``bench/peaks.json``), and
the count module of a configuration's model family
(``bench/costs/<family_module>.py``: FLOPs and bytes from its widths)."""
from __future__ import annotations

import importlib
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def counts(family_module: str):
    return importlib.import_module(f"bench.costs.{family_module}")


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """Per-chip peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
