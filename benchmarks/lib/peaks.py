"""Published peaks of the chips the benchmark knows, keyed by
``device_kind``.  A device that is not in ``peaks.json`` is an error,
never a default."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it "
            f"to {_PATH} with its source (known: {sorted(table)})")
    return table[device_kind]
