"""A filtered, projected read through the served query entry
(``ServeClient.query`` over the socket; ``DatasetServer.query`` for the
warm-up), checked against the plain NumPy reference of ``bench/query.py``.

An op file gives:

  columns(spec)            table columns the reference needs
  warm(server, spec)       serve the request once in-process
  send(client, spec)       (rows, the server's wall seconds, digest),
                           in the load generator, which has no JAX
  expected(spec, table, cache)  the digest a correct answer has; ``cache``
                           is a dict the op may keep indexes in
"""

import numpy as np

from bench.query import digest, predicate, reference_answer


def columns(spec: dict) -> list:
    return spec["columns"] + [w[0] for w in spec["where"]]


def warm(server, spec: dict) -> None:
    server.query("table", columns=spec["columns"], where=predicate(spec),
                 head=spec.get("head"), tenant=spec["class"])


def send(client, spec: dict) -> tuple:
    res = client.query("table", columns=spec["columns"],
                       where=predicate(spec), head=spec.get("head"),
                       tenant=spec["class"])
    return res.rows, res.wall_seconds, digest(spec["columns"], res.table)


def expected(spec: dict, table: dict, cache: dict) -> str:
    return digest(spec["columns"], reference_answer(
        spec, table, _point_rows(spec, table, cache)))


def _point_rows(spec: dict, table: dict, cache: dict):
    """Rows of a single-equality spec through a sorted index of its column
    (a plain binary search), else None (a full reference scan)."""
    where = spec["where"]
    if len(where) != 1 or where[0][1] != "==" or spec.get("head") is not None:
        return None
    col, _, v = where[0]
    if col not in cache:
        order = np.argsort(table[col], kind="stable")
        cache[col] = (order, table[col][order])
    order, keys = cache[col]
    lo = np.searchsorted(keys, v, "left")
    hi = np.searchsorted(keys, v, "right")
    return np.sort(order[lo:hi])
