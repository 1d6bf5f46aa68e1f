"""The work a pass must do, counted from shapes alone, and the peaks it
is read against (``cells/peaks.json``, keyed by JAX's ``device_kind``).

The count does not depend on which kernel runs: a later executor of
the same merge is read against the same bytes.
"""

from __future__ import annotations

import json
import os

#: Bytes per key of one replica row as the changeset hands it over:
#: logical time 8, node 4, value 8, tombstone 1, valid 1.
ROW_BYTES = 8 + 4 + 8 + 1 + 1
#: Bytes per slot of the store's lanes: lt 8, node 4, val 8, mod_lt 8,
#: mod_node 4, occupied 1, tomb 1.
STORE_BYTES = 8 + 4 + 8 + 8 + 4 + 1 + 1


def fanin_bytes(replicas: int, keys: int) -> int:
    """Least HBM traffic of one fan-in pass: every replica row read
    once, the store's lanes read once and written once."""
    return replicas * keys * ROW_BYTES + 2 * keys * STORE_BYTES


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"cells/peaks.json (known: {sorted(table)})")
    return table[device_kind]
