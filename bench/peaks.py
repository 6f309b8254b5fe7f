"""The table of chip peaks (``peaks.json``), keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str, table: Path = TABLE) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error,
    never a default."""
    known = json.loads(Path(table).read_text())
    if device_kind not in known:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{table} (known: {sorted(known)})")
    return known[device_kind]
