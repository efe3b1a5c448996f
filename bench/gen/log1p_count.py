"""``log(1 + x)`` of a count that is 0 with probability ``p_zero`` and
else ``round(exp(N(mu, sigma)))``."""

import numpy as np


def column(ctx, g):
    rng, n = ctx.rng, ctx.n
    cnt = np.rint(np.exp(rng.normal(float(g["mu"]), float(g["sigma"]), n)))
    cnt[rng.random(n) < float(g["p_zero"])] = 0
    return np.log1p(cnt).astype(ctx.dtype)
