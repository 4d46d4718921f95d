"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

The table is ``peaks.json``, with its source.  A device that is not in
it is an error, never a default: a roofline share against the wrong
peak is worse than none.
"""

from __future__ import annotations

import json
import os
from typing import Dict

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> Dict[str, float]:
    with open(_TABLE) as f:
        table = json.load(f)["devices"]
    try:
        return dict(table[device_kind])
    except KeyError:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; known: {sorted(table)}") from None
