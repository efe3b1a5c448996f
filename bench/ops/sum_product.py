"""An aggregate through the served aggregate entry (``ServeClient.aggregate``
over the socket; ``DatasetServer.aggregate`` for the warm-up): the exact
``sum(a * b)`` of the spec's two ``columns`` over the rows its ``where``
admits, checked against a plain NumPy evaluation over the generated table.

A ``where`` entry is ``[column, op, value]`` with an op of
``bench/query.py``, or a two-sided range whose value is ``[lo, hi]``:
``"[]"`` for ``lo <= x <= hi``, ``"[)"`` for ``lo <= x < hi``. So TPC-H Q6
is ``[["l_shipdate", "[)", [DATE, DATE + 1 year]], ["l_discount", "[]",
[DISCOUNT - 0.01, DISCOUNT + 0.01]], ["l_quantity", "<", QUANTITY]]`` with
decimals in hundredths and dates in days since 1970-01-01.

An answer is the digest of (value, matched rows).
"""

import hashlib
import operator

import numpy as np

from bench.query import OPS

RANGES = {"[]": (operator.ge, operator.le), "[)": (operator.ge, operator.lt)}


def columns(spec: dict) -> list:
    return spec["columns"] + [w[0] for w in spec["where"]]


def _terms(spec: dict):
    """``(column, op, value)`` comparisons whose conjunction is the
    ``where``."""
    for col, op, v in spec["where"]:
        if op in RANGES:
            yield col, RANGES[op][0], v[0]
            yield col, RANGES[op][1], v[1]
        else:
            yield col, OPS[op], v


def predicate(spec: dict):
    from repro.scan import C
    pred = None
    for col, op, v in _terms(spec):
        term = op(C(col), v)
        pred = term if pred is None else pred & term
    return pred


def digest(value: int, rows: int) -> str:
    return hashlib.sha256(f"{int(value)}:{int(rows)}".encode()).hexdigest()


def warm(server, spec: dict) -> None:
    server.aggregate("table", sum_product=spec["columns"],
                     where=predicate(spec), tenant=spec["class"])


def send(client, spec: dict) -> tuple:
    res = client.aggregate("table", sum_product=spec["columns"],
                           where=predicate(spec), tenant=spec["class"])
    return res.rows, res.wall_seconds, digest(res.value, res.rows)


def expected(spec: dict, table: dict, cache: dict) -> str:
    a, b = spec["columns"]
    mask = np.ones(len(table[a]), bool)
    for col, op, v in _terms(spec):
        mask &= op(table[col], v)
    prod = table[a][mask].astype(np.int64) * table[b][mask].astype(np.int64)
    return digest(sum(prod.tolist()), int(mask.sum()))
