"""``round(exp(N(mu, sigma)))`` clipped to ``lo`` and ``hi``."""

import numpy as np


def column(ctx, g):
    x = np.rint(np.exp(ctx.rng.normal(float(g["mu"]), float(g["sigma"]),
                                      ctx.n)))
    return np.clip(x, g["lo"], g["hi"]).astype(ctx.dtype)
