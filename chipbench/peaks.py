"""Published peaks of each accelerator, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> dict:
    """The peaks row of ``device_kind``; a device not in the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    try:
        return table[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}") from None
