"""The chip's published peaks, keyed by the ``device_kind`` JAX reports."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PATH) -> dict:
    """Peaks of one chip of this kind; a kind not in the table is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path} "
                       f"(known: {sorted(table['devices'])})")
    return dict(table["devices"][device_kind], source=table["source"])
