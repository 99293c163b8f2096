"""Published peaks of a chip, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def for_kind(kind: str) -> dict:
    """The peaks of ``kind``; a kind not in the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if kind not in table["kinds"]:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(table['kinds'])}")
    return table["kinds"][kind]
