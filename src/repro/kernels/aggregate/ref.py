"""Plain NumPy oracle for the fused filter-and-sum kernel.

The TPC-H Q6 shape with no kernel, no staging and no limbs: a row counts
when every column lies inside its closed interval; the answer is the sum of
``int64(a) * int64(b)`` over those rows, as a Python int, and their count.
Decimals are stored as exact integers (cents, hundredths), so this is
TPC-H's decimal arithmetic with no departure.
"""

from __future__ import annotations

import numpy as np


def sum_product_ref(cols, lo, hi, a: int, b: int) -> tuple[int, int]:
    """cols: int[K, N]; lo, hi: int[K]; ``a``, ``b``: factor rows of
    ``cols`` -> (sum of products over the matching rows, matching rows)."""
    cols = np.asarray(cols)
    lo = np.asarray(lo).reshape(-1, 1)
    hi = np.asarray(hi).reshape(-1, 1)
    mask = ((cols >= lo) & (cols <= hi)).all(axis=0)
    prod = cols[a][mask].astype(np.int64) * cols[b][mask].astype(np.int64)
    return sum(prod.tolist()), int(mask.sum())
