"""The table of peaks, keyed by JAX's ``device_kind``.  A device that is not in
the table is an error, never a default."""

from __future__ import annotations

import json
import pathlib

TABLE = pathlib.Path(__file__).with_name("peaks.json")


class UnknownDevice(LookupError):
    pass


def peaks_for(device_kind: str) -> dict:
    table = json.loads(TABLE.read_text())["peaks"]
    if device_kind not in table:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in {TABLE.name} "
            f"(known: {sorted(table)})")
    return table[device_kind]
