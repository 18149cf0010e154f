"""Published peaks of the chip, from ``peaks.json``, keyed by ``device_kind``."""
import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind: str) -> dict:
    """Peaks of ``device_kind``; a device not in the table is an error."""
    with open(PATH) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PATH}; known: {sorted(table)}")
    return table[device_kind]
