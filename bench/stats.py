"""Rate and percentile arithmetic over a run's request records.

Every request the plan sent counts: a latency runs from the request's due
time to its answer, and a failed request counts as attempted and as slower
than any answer (``inf``), so it can only raise a percentile.
"""

from __future__ import annotations

import math
from typing import Iterable, Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p``% of
    the values at or below it."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    k = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[k - 1]


def latencies(records: Iterable, cls: str | None = None,
              specs: Sequence[dict] | None = None) -> list[float]:
    """Seconds from due to done for every record (of spec class ``cls``
    when given); failed requests are ``inf``."""
    out = []
    for r in records:
        if cls is not None and specs[r["qid"]]["class"] != cls:
            continue
        out.append(r["done"] - r["due"] if r["ok"] else math.inf)
    return out


def window(records: Sequence, t0: float) -> tuple[float, float]:
    """The measured window: from the start instant to the last answer."""
    return t0, max([t0] + [r["done"] for r in records])


def rows_per_second(records: Sequence, t0: float, rows_of) -> float:
    """Rows covered by every completed request over the whole window;
    ``rows_of(record)`` is what one request covers."""
    start, end = window(records, t0)
    if end <= start:
        return 0.0
    return sum(rows_of(r) for r in records if r["ok"]) / (end - start)
