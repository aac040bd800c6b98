"""Published peaks of each chip, keyed by JAX's ``device_kind``
(``peaks.json``, with the source of each entry). A chip that is not in the
table is an error, not a default."""
import json
from pathlib import Path

_TABLE = Path(__file__).with_name("peaks.json")


def peak(device_kind: str) -> dict:
    table = json.loads(_TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {_TABLE.name}")
    return table[device_kind]
