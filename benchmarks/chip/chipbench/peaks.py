"""The chips' published peaks, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The row of ``peaks.json`` for ``device_kind``; a kind that is not
    in the table is an error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; add its published peaks with a source")
    return table[device_kind]
