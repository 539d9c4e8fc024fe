"""Published peaks of the chip a run is on, keyed by JAX's ``device_kind``."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, table: Path = TABLE) -> dict:
    """The row of ``device_kind``; a device not in the table is an error."""
    with open(table) as f:
        rows = json.load(f)
    if device_kind not in rows:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(rows)}")
    return rows[device_kind]
