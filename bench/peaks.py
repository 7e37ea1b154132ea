"""The chip's published peaks, keyed by ``device_kind`` (``peaks.json``)."""

from __future__ import annotations

import json
from pathlib import Path

_FILE = Path(__file__).with_name("peaks.json")


class UnknownDevice(KeyError):
    pass


def for_kind(kind: str) -> dict:
    table = json.loads(_FILE.read_text())["devices"]
    if kind not in table:
        raise UnknownDevice(f"no published peaks for device kind {kind!r} "
                            f"in {_FILE.name}; known: {sorted(table)}")
    return table[kind]
