"""0 or 1, 1 with probability ``p``."""


def column(ctx, g):
    return (ctx.rng.random(ctx.n) < float(g["p"])).astype(ctx.dtype)
