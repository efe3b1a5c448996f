"""The HBM bytes one call of the fused filter-and-sum kernel
(``jit_sum_product_pallas``) must move. Peaks and the least time are
``bench/roofline.py``'s."""

from __future__ import annotations

BLOCK_N = 8192       # the kernel's rows per program (64 sublanes x 128)
LANES = 128
PARTS = 3            # partial sums: low limb, high limb, matched rows


def padded_rows(rows: int) -> int:
    return max(BLOCK_N, -(-rows // BLOCK_N) * BLOCK_N)


def sum_product_bytes(columns: int, rows: int) -> int:
    """The int32 columns (predicate columns and factors, each once) padded
    to the kernel's row block, read; the (3, 8, 128) int32 partial sums,
    written; and each column's bounds and the row count, laid along the
    lanes, read."""
    return (4 * columns * padded_rows(rows) + 4 * PARTS * 8 * LANES
            + 4 * LANES * (2 * columns + 1))
