"""Captions: ``Poisson(mean_words)`` words (at least ``min_words``) from a
Zipf(``s``) vocabulary of ``vocab`` words of 2..``max_word`` letters,
joined by spaces."""

import numpy as np

from bench.generate import join_pieces, word_pool, zipf_ranks


def column(ctx, g):
    rng, n = ctx.rng, ctx.n
    pool, wst, wln = word_pool(rng, int(g["vocab"]), int(g["max_word"]))
    pool = np.concatenate([pool, np.frombuffer(b" ", np.uint8)])
    space = len(pool) - 1
    k = np.maximum(int(g.get("min_words", 1)),
                   rng.poisson(float(g["mean_words"]), n))
    tok = zipf_ranks(rng, len(wst), int(k.sum()), float(g["s"]))
    # word, space, word, ... : 2k - 1 pieces per row
    pieces = 2 * k - 1
    starts = np.full(int(pieces.sum()), space, np.int64)
    lens = np.ones(len(starts), np.int64)
    first = np.concatenate([[0], np.cumsum(pieces)[:-1]])
    row_of_tok = np.repeat(np.arange(n), k)
    tok_pos = np.arange(len(tok)) - np.repeat(
        np.concatenate([[0], np.cumsum(k)[:-1]]), k)
    at = first[row_of_tok] + 2 * tok_pos
    starts[at] = wst[tok]
    lens[at] = wln[tok]
    return join_pieces(pool, starts, lens, pieces)
