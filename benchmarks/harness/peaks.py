"""The table of peaks, keyed by device_kind. An unknown kind is an error."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    kinds = sorted(k for k in table if not k.startswith("_"))
    if device_kind not in kinds:
        raise KeyError(f"device_kind {device_kind!r} is not in {PEAKS_FILE.name} "
                       f"({kinds}): add its published peaks with their source "
                       "instead of pricing it as another chip")
    return table[device_kind]
