"""int64 ``(a * global row + b) mod 2**62`` with odd ``a`` and ``b`` drawn
from the seed: unique, unclustered keys."""

import numpy as np

from bench.generate import column_rng

MASK62 = (1 << 62) - 1


def column(ctx, g):
    ab = column_rng(ctx.seed, -1 % (1 << 32), ctx.index).integers(
        0, 1 << 62, 2)
    a, b = int(ab[0]) | 1, int(ab[1])
    rows = np.arange(ctx.first, ctx.first + ctx.n, dtype=np.uint64)
    key = (rows * np.uint64(a) + np.uint64(b)) & np.uint64(MASK62)
    return key.astype(np.int64)
