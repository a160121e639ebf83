"""The chips' published peaks (``peaks.json``), keyed by the
``device_kind`` JAX reports. A device that is not in the table is an
error, never a default."""

from __future__ import annotations

import json
import pathlib

TABLE = pathlib.Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str, table: pathlib.Path = TABLE) -> dict:
    devices = json.loads(table.read_text())["devices"]
    try:
        return dict(devices[device_kind])
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {table.name} "
            f"(known: {', '.join(sorted(devices))})") from None
