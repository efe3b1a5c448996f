"""Query specs, the plain reference that answers them, and answer digests.

A query spec is plain JSON: ``{"columns": [...], "where": [[col, op,
value], ...], "head": n or None}``, the ``where`` list a conjunction. The
load generator turns it into a ``repro.scan`` predicate; the reference
answers it with NumPy over the arrays the generator made, which is all it
shares with the program under test. Both sides reduce an answer to the
same digest, so every answer is compared exactly.
"""

from __future__ import annotations

import hashlib
import operator

import numpy as np

OPS = {">=": operator.ge, "<=": operator.le, ">": operator.gt,
       "<": operator.lt, "==": operator.eq}

# storage quantization as the reference holds it: the float32 value
# rounded to the stored type and read back as float32
_STORED = {"bf16": "bfloat16", "fp8_e4m3": "float8_e4m3fn"}


def predicate(spec: dict):
    """The spec's ``where`` as a ``repro.scan`` predicate (None if empty)."""
    from repro.scan import C
    pred = None
    for col, op, value in spec.get("where") or ():
        term = OPS[op](C(col), value)
        pred = term if pred is None else pred & term
    return pred


def stored_table(cfg: dict, table: dict) -> dict:
    """The generated table as the store serves it back: quantized columns
    rounded to their stored type."""
    import ml_dtypes
    out = dict(table)
    for c in cfg["columns"]:
        q = c.get("quant")
        if q and c["name"] in out:
            stored = np.dtype(getattr(ml_dtypes, _STORED[q]))
            out[c["name"]] = np.asarray(out[c["name"]], np.float32) \
                .astype(stored).astype(np.float32)
    return out


def reference_rows(spec: dict, table: dict) -> np.ndarray:
    """Row indices (table order) that answer the spec. Comparisons are
    exact: floats compare in float64, integers as integers."""
    n = len(next(iter(table.values())))
    mask = np.ones(n, bool)
    for col, op, value in spec.get("where") or ():
        x = table[col]
        if x.dtype.kind == "f":
            x = x.astype(np.float64)
        mask &= OPS[op](x, value)
    rows = np.flatnonzero(mask)
    if spec.get("head") is not None:
        rows = rows[:int(spec["head"])]
    return rows


def reference_answer(spec: dict, table: dict,
                     rows: np.ndarray | None = None) -> dict:
    if rows is None:
        rows = reference_rows(spec, table)
    out = {}
    for c in spec["columns"]:
        col = table[c]
        out[c] = col[rows] if isinstance(col, np.ndarray) \
            else [col[i] for i in rows.tolist()]
    return out


def digest(columns, table: dict) -> str:
    """sha256 over the columns in order: name, then a scalar column's
    dtype and bytes, or a string column's lengths and bytes."""
    h = hashlib.sha256()
    for c in columns:
        col = table[c]
        h.update(c.encode() + b"\0")
        if isinstance(col, np.ndarray):
            h.update(col.dtype.str.encode() + b"\0")
            h.update(np.ascontiguousarray(col).tobytes())
        else:
            h.update(b"|bytes\0")
            h.update(np.fromiter(map(len, col), np.int64, len(col)).tobytes())
            h.update(b"".join(col))
    return h.hexdigest()
