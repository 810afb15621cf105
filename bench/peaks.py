"""Published per-chip peaks, keyed by ``jax.Device.device_kind``."""
from __future__ import annotations

import json
import pathlib

_TABLE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table row of one device kind; a kind that is not in the
    table is an error, never a default."""
    table = json.loads(_TABLE.read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {_TABLE.name}; known: "
                       f"{sorted(table['devices'])}") from None
