"""Chip peaks by ``device_kind``, from ``bench/peaks.json``."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parent.parent / "peaks.json"


class UnknownDevice(KeyError):
    """A device kind the peak table does not list."""


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> Dict[str, float]:
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; "
                            f"bench/peaks.json lists {sorted(table)}")
    return dict(table[device_kind])
