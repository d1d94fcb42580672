"""The chip's published peaks, from ``bench/peaks.json``, by ``device_kind``.
A chip that is not in the table is an error, never a default."""
from __future__ import annotations

import json

from harness.spec import BENCH


def lookup(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in bench/peaks.json; have {sorted(table)}"
        )
    return table[device_kind]
