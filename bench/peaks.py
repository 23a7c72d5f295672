"""Published peaks of the chip, by ``device_kind`` (``bench/peaks.json``).
A device that is not in the table is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, table: Path = TABLE) -> dict:
    known = json.loads(table.read_text())
    if device_kind not in known:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {table.name}"
                       f" (known: {sorted(known)})")
    return known[device_kind]
