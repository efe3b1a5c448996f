"""Published chip peaks and the bytes each kernel must move.

``peaks.json`` is keyed by JAX's ``device_kind``; a device that is not in
it is an error, never a default.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
BLOCK_N = 2048       # the range-filter kernel's rows per program


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    with open(path) as f:
        table = json.load(f)
    try:
        return table[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}") from None


def range_mask_bytes(columns: int, rows: int) -> int:
    """HBM bytes one range-filter call must move: the float32 columns
    padded to the kernel's row block, the uint8 mask it writes, and the
    float32 lower and upper bound of each column."""
    n_pad = -(-rows // BLOCK_N) * BLOCK_N
    return 4 * columns * n_pad + n_pad + 8 * columns


def least_seconds(nbytes: float, device_kind: str) -> float:
    """The memory-bound least time to move ``nbytes``."""
    return nbytes / float(peaks(device_kind)["hbm_bytes_per_s"])
