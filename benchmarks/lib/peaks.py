"""The table of published peaks (``peaks.json``), keyed by ``device_kind``.
A device that is not in the table is an error, never a default."""

from __future__ import annotations

import os
from typing import Dict

from .manifest import BENCH_DIR, load_json


def peaks_for(device_kind: str, path: str = None) -> Dict[str, float]:
    table = load_json(path or os.path.join(BENCH_DIR, "peaks.json"))
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in peaks.json "
            f"(known: {sorted(table['devices'])}); a share of an unknown "
            "peak is no number") from None
