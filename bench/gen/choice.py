"""Strings ``values`` drawn with probabilities ``p``."""


def column(ctx, g):
    vals = [v.encode() for v in g["values"]]
    idx = ctx.rng.choice(len(vals), ctx.n, p=g["p"])
    return [vals[i] for i in idx.tolist()]
