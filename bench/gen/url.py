"""``https://<host>.com/<prefix>/<slug><ext>``: hosts and prefixes from
Zipf(``s``)-popular pools of ``hosts`` and ``prefixes`` words, a slug of
``Poisson(mean_slug)`` letters and digits, an extension from ``ext`` with
probabilities ``ext_p``."""

import numpy as np

from bench.generate import ALNUM, join_pieces, word_pool, zipf_ranks


def column(ctx, g):
    rng, n = ctx.rng, ctx.n
    hp, hst, hln = word_pool(rng, int(g["hosts"]), 12)
    pp, pst, pln = word_pool(rng, int(g["prefixes"]), 10)
    slug_pool = rng.choice(ALNUM, 1 << 20)
    fixed = [b"https://", b".com/", b"/"] + [e.encode() for e in g["ext"]]
    fixed_pool = np.frombuffer(b"".join(fixed), np.uint8)
    fst = np.concatenate([[0], np.cumsum([len(x) for x in fixed])[:-1]])
    fln = np.asarray([len(x) for x in fixed])
    base_h = len(fixed_pool)
    base_p = base_h + len(hp)
    base_s = base_p + len(pp)
    pool = np.concatenate([fixed_pool, hp, pp, slug_pool])
    host = zipf_ranks(rng, len(hst), n, float(g["s"]))
    pre = zipf_ranks(rng, len(pst), n, float(g["s"]))
    slug_len = np.clip(rng.poisson(float(g["mean_slug"]), n), 4,
                       len(slug_pool) // 2)
    slug_at = rng.integers(0, len(slug_pool) - slug_len.max(), n)
    ext = rng.choice(len(g["ext"]), n, p=g["ext_p"]) + 3
    # https:// host .com/ prefix / slug ext : 7 pieces per row
    starts = np.stack([np.full(n, fst[0]), base_h + hst[host],
                       np.full(n, fst[1]), base_p + pst[pre],
                       np.full(n, fst[2]), base_s + slug_at, fst[ext]], 1)
    lens = np.stack([np.full(n, fln[0]), hln[host], np.full(n, fln[1]),
                     pln[pre], np.full(n, fln[2]), slug_len, fln[ext]], 1)
    return join_pieces(pool, starts.reshape(-1), lens.reshape(-1),
                       np.full(n, 7))
