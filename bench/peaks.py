"""The chip's published peaks, by the ``device_kind`` JAX reports."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Peak rates of ``device_kind``; a kind missing from the table is an
    error, never a default."""
    devices = json.loads(TABLE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{TABLE.name}; known: {sorted(devices)}")
    return devices[device_kind]
