"""Beta(``a``, ``b``) times an optional ``scale``."""


def column(ctx, g):
    x = ctx.rng.beta(float(g["a"]), float(g["b"]), ctx.n) \
        * float(g.get("scale", 1))
    return x.astype(ctx.dtype)
