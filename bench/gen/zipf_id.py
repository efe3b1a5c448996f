"""Ids ``0..cardinality-1`` with Zipf(``s``) popularity, scrambled by a
fixed affine permutation so that popular ids are not small numbers."""

import numpy as np

from bench.generate import zipf_ranks


def column(ctx, g):
    card = int(g["cardinality"])
    ranks = zipf_ranks(ctx.rng, card, ctx.n, float(g["s"]))
    mult = 2654435761 % card or 1
    while np.gcd(mult, card) != 1:
        mult += 1
    return ((ranks.astype(np.int64) * mult + ctx.index) % card).astype(
        ctx.dtype)
