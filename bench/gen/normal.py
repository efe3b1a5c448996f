"""Normal(``mean``, ``std``), clipped to optional ``lo`` and ``hi``."""

import numpy as np


def column(ctx, g):
    x = ctx.rng.normal(float(g["mean"]), float(g["std"]), ctx.n)
    return np.clip(x, g.get("lo", -np.inf), g.get("hi", np.inf)).astype(
        ctx.dtype)
