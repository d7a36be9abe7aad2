"""Published peaks of each chip, keyed by ``device_kind`` as JAX reports
it.  A device that is not in ``peaks.json`` is an error, not a default."""
from __future__ import annotations

import json

from .spec import HERE


class UnknownDevice(KeyError):
    pass


def peaks(device_kind, path=HERE / "peaks.json"):
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path.name}; "
            f"it has {sorted(table)}")
    return table[device_kind]
