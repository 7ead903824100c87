"""The table of peaks, keyed by `device_kind` exactly as JAX reports it.
A kind that is not in the table is an error, never another chip's peak."""

from __future__ import annotations

import json
import pathlib

_TABLE = pathlib.Path(__file__).with_name("peaks.json")


def peak(device_kind: str, what: str) -> float:
    with _TABLE.open() as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; the "
            f"table has {sorted(table)}")
    return float(table[device_kind][what])
