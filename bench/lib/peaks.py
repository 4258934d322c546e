"""Published peaks of each chip, keyed by JAX's ``device_kind``. A chip
that is not in ``peaks.json`` is an error, never a default."""
from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
