"""The chip's published peaks, by ``device_kind`` (``bench/peaks.json``)."""
from __future__ import annotations

import json
import pathlib
from typing import Dict

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> Dict[str, float]:
    """bf16 FLOP/s, HBM bytes/s and HBM bytes of one chip; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]
