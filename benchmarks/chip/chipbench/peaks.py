"""Chip peaks, keyed by JAX's `device_kind` (`peaks.json` says whence).
A device that is not in the table is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

_TABLE = json.loads((Path(__file__).with_name("peaks.json")).read_text())


def peaks(device_kind: str) -> Dict[str, float]:
    devices = _TABLE["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(devices)}")
    return dict(devices[device_kind])
