"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A chip that is not in ``peaks.json`` is an
error, not a default."""
from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).with_name("peaks.json")


def of(device_kind: str) -> dict:
    table = json.loads(_TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
