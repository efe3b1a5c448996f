"""int64 ``start + global row``: ids in part order, so clustered."""

import numpy as np


def column(ctx, g):
    return (int(g.get("start", 0)) + ctx.first
            + np.arange(ctx.n, dtype=np.int64)).astype(ctx.dtype)
