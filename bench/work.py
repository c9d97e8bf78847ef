"""The work one call needs, computed from a configuration's declared fields.

Nothing here asks the engine what it did: the needed bytes are one read of
every field the configuration reads and one write of every field it writes
per call, whatever the number of sweeps (the temporal-blocking ideal), per
chip, from the per-chip domain.  The least time of a call is those bytes at
peak HBM bandwidth; a flop term waits for a measured vector rate (the chip
publishes none for f32, and ``peak_probe.py`` measures it).
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def points_per_chip(config: dict) -> int:
    return math.prod(config["per_chip"])


def points(config: dict) -> int:
    return math.prod(config["shape"])


def needed_bytes(config: dict) -> int:
    """HBM bytes one call must move on each chip."""
    fields = len(config["reads"]) + len(config["writes"])
    return fields * np.dtype(config["dtype"]).itemsize * points_per_chip(config)


def peak(device_kind: str, path: Path = PEAKS) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_seconds(config: dict, device_kind: str) -> float:
    """The least time one call can take on one chip: the needed bytes over
    peak HBM bandwidth."""
    return needed_bytes(config) / peak(device_kind)["hbm_bytes_per_s"]
