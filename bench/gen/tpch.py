"""TPC-H ``LINEITEM`` columns as TPC-H Standard Specification Clause 4.2.3
populates them: ``{"kind": "tpch", "column": "l_shipdate", ...}``.

The configuration lists the 16 columns in Clause 1.4's order, so a column's
index names it. A column that the specification derives from another
(``l_suppkey`` from ``l_partkey``, ``l_extendedprice`` from ``l_quantity``
and ``l_partkey``, the dates and flags from the order date) rebuilds that
column from its own stream (``bench.generate.column_rng`` with its index),
so any column alone comes out bit for bit as in the whole table.

Orders: ``l_orderkey``'s stream draws each order's line count, uniform in
[1, 7], and its order date, uniform in [STARTDATE, ENDDATE - 151 days]; a
shard's rows are the lines of its orders in turn, the last order cut at
the shard's end. Order numbers of shard ``s`` start at ``s * rows + 1``
(at most one order a row) and keys are made sparse as the specification's
``dbgen`` makes them: the first 8 of every 32. Decimals are exact integers
(cents, hundredths), dates int32 days since 1970-01-01.

Parameters: ``parts`` (SF * 200,000) for ``l_partkey``, ``l_suppkey`` and
``l_extendedprice``; ``suppliers`` (SF * 10,000) for ``l_suppkey``.
"""

import datetime

import numpy as np

from bench.generate import column_rng

COLUMNS = ("l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
           "l_quantity", "l_extendedprice", "l_discount", "l_tax",
           "l_returnflag", "l_linestatus", "l_shipdate", "l_commitdate",
           "l_receiptdate", "l_shipinstruct", "l_shipmode", "l_comment")
_EPOCH = datetime.date(1970, 1, 1)
STARTDATE = (datetime.date(1992, 1, 1) - _EPOCH).days
CURRENTDATE = (datetime.date(1995, 6, 17) - _EPOCH).days
ENDDATE = (datetime.date(1998, 12, 31) - _EPOCH).days
INSTRUCTIONS = (b"DELIVER IN PERSON", b"COLLECT COD", b"NONE",
                b"TAKE BACK RETURN")
MODES = (b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB")
# the words of Clause 4.2.2.14's text grammar
WORDS = (
    "foxes ideas theodolites pinto beans instructions dependencies excuses "
    "platelets asymptotes courts dolphins multipliers sauternes warthogs "
    "frets dinos attainments somas Tiresias patterns forges braids hockey "
    "players frays warhorses dugouts notornis epitaphs pearls tithes waters "
    "orbits gifts sheaves depths sentiments decoys realms pains grouches "
    "escapades sleep wake are cajole haggle nag use boost affix detect "
    "integrate maintain nod was lose sublate solve thrash promise engage "
    "hinder print x-ray breach eat grow impress mold poach serve run dazzle "
    "snooze doze unwind kindle play hang believe doubt furious sly careful "
    "blithe quick fluffy slow quiet ruthless thin close dogged daring brave "
    "stealthy permanent enticing idle busy regular final ironic even bold "
    "silent sometimes always never furiously slyly carefully blithely "
    "quickly fluffily slowly quietly ruthlessly thinly closely doggedly "
    "daringly bravely stealthily permanently enticingly idly busily "
    "regularly finally ironically evenly boldly silently about above "
    "according to across after against along alongside of among around at "
    "atop before behind beneath beside besides between beyond by despite "
    "during except for from in place of inside instead of into near on "
    "outside over past since through throughout toward under until up upon "
    "without with within do may might shall will would can could should "
    "ought must have need try").split()
POOL_BYTES = 1 << 20                # per shard: the comments' text pool


def _stream(ctx, name: str) -> np.random.Generator:
    """Column ``name``'s own stream in this shard."""
    return column_rng(ctx.seed, ctx.first // ctx.n, COLUMNS.index(name))


def _orders(ctx):
    """Per row: its order's number in the shard, its line number and its
    order date."""
    rng = _stream(ctx, "l_orderkey")
    lines = rng.integers(1, 8, ctx.n)           # n orders fill n rows
    odate = rng.integers(STARTDATE, ENDDATE - 151 + 1, ctx.n)
    order = np.repeat(np.arange(ctx.n), lines)[:ctx.n]
    first = np.concatenate([[0], np.cumsum(lines)[:-1]])
    return order, np.arange(ctx.n) - first[order] + 1, odate[order]


def _partkey(ctx, g):
    return _stream(ctx, "l_partkey").integers(1, int(g["parts"]) + 1, ctx.n)


def _quantity(ctx, g):
    return _stream(ctx, "l_quantity").integers(1, 51, ctx.n)


def retail_cents(partkey):
    """P_RETAILPRICE in cents: 90000 + ((partkey / 10) mod 20001) + 100 *
    (partkey mod 1000)."""
    pk = np.asarray(partkey, np.int64)
    return 90_000 + (pk // 10) % 20_001 + 100 * (pk % 1000)


def _suppkey(ctx, g):
    pk = _partkey(ctx, g)
    s = int(g["suppliers"])
    i = _stream(ctx, "l_suppkey").integers(0, 4, ctx.n)
    return (pk + i * (s // 4 + (pk - 1) // s)) % s + 1


def _shipdate(ctx, g):
    return _orders(ctx)[2] + _stream(ctx, "l_shipdate").integers(1, 122,
                                                                 ctx.n)


def _receiptdate(ctx, g):
    return _shipdate(ctx, g) + _stream(ctx, "l_receiptdate").integers(
        1, 31, ctx.n)


def _returnflag(ctx, g):
    ra = _stream(ctx, "l_returnflag").integers(0, 2, ctx.n)
    late = _receiptdate(ctx, g) > CURRENTDATE
    return [b"N" if n else (b"R" if r else b"A")
            for n, r in zip(late.tolist(), ra.tolist())]


def _linestatus(ctx, g):
    return [b"O" if x else b"F"
            for x in (_shipdate(ctx, g) > CURRENTDATE).tolist()]


def _comment(ctx, g):
    rng = _stream(ctx, "l_comment")
    words = [w.encode() for w in WORDS]
    picks = rng.integers(0, len(words), POOL_BYTES // 4)
    pool = b" ".join(words[i] for i in picks.tolist())[:POOL_BYTES]
    lens = rng.integers(10, 44, ctx.n)
    at = rng.integers(0, len(pool) - 43, ctx.n)
    return [pool[a:a + n] for a, n in zip(at.tolist(), lens.tolist())]


def _choice(name, values):
    def make(ctx, g):
        idx = _stream(ctx, name).integers(0, len(values), ctx.n)
        return [values[i] for i in idx.tolist()]
    return make


_MAKE = {
    "l_orderkey": lambda ctx, g: _sparse(ctx.first + _orders(ctx)[0] + 1),
    "l_partkey": _partkey,
    "l_suppkey": _suppkey,
    "l_linenumber": lambda ctx, g: _orders(ctx)[1],
    "l_quantity": _quantity,
    "l_extendedprice": lambda ctx, g: _quantity(ctx, g) * retail_cents(
        _partkey(ctx, g)),
    "l_discount": lambda ctx, g: _stream(ctx, "l_discount").integers(
        0, 11, ctx.n),
    "l_tax": lambda ctx, g: _stream(ctx, "l_tax").integers(0, 9, ctx.n),
    "l_returnflag": _returnflag,
    "l_linestatus": _linestatus,
    "l_shipdate": _shipdate,
    "l_commitdate": lambda ctx, g: _orders(ctx)[2] + _stream(
        ctx, "l_commitdate").integers(30, 91, ctx.n),
    "l_receiptdate": _receiptdate,
    "l_shipinstruct": _choice("l_shipinstruct", INSTRUCTIONS),
    "l_shipmode": _choice("l_shipmode", MODES),
    "l_comment": _comment,
}


def _sparse(i):
    """``dbgen``'s sparse order keys: keep the first 8 of every 32."""
    i = np.asarray(i, np.int64)
    return ((i >> 3) << 5) | (i & 7)


def column(ctx, g):
    name = g["column"]
    if COLUMNS.index(name) != ctx.index:
        raise ValueError(f"tpch: {name!r} is column {COLUMNS.index(name)} "
                         f"in Clause 1.4's order, not {ctx.index}")
    out = _MAKE[name](ctx, g)
    return out if ctx.dtype is None else np.asarray(out).astype(ctx.dtype)
