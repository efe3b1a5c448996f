"""The program's host spans, grouped by the served query they belong to.

The server runs each query on one pool thread: ``prepare`` (the ``plan.*``
spans on a plan-cache miss) and then a ``serve.query`` span whose tenant
the load generator set to the request's class. A span belongs to the
``serve.query`` on its thread that encloses it; a plan span belongs to the
next ``serve.query`` its thread opens.
"""

from __future__ import annotations

import bisect
from collections import defaultdict

PLAN_PREFIXES = ("plan.", "scan.plan")


def by_query(spans: list[dict]) -> list[dict]:
    """``[{"cls", "query": span, "children": [spans]}]`` for every
    ``serve.query`` span. Spans are dicts with ``name``, ``start``,
    ``end`` (seconds, ``perf_counter``), ``tid`` and ``args``."""
    per_thread = defaultdict(list)
    for s in spans:
        per_thread[s["tid"]].append(s)
    out = []
    for tid, ss in per_thread.items():
        ss.sort(key=lambda s: s["start"])
        queries = [s for s in ss if s["name"] == "serve.query"]
        starts = [q["start"] for q in queries]
        groups = [{"cls": q["args"].get("tenant"), "query": q,
                   "children": []} for q in queries]
        for s in ss:
            if s["name"] == "serve.query":
                continue
            if s["name"].startswith(PLAN_PREFIXES):
                i = bisect.bisect_left(starts, s["end"])
            else:
                i = bisect.bisect_right(starts, s["start"]) - 1
                if i >= 0 and s["end"] > queries[i]["end"]:
                    i = -1
            if 0 <= i < len(groups):
                groups[i]["children"].append(s)
        out.extend(groups)
    return out


def seconds_per_query(spans: list[dict], cls: str, names) -> float | None:
    """Mean over the class's queries of the summed duration of their spans
    whose name is in ``names``."""
    qs = [g for g in by_query(spans) if g["cls"] == cls]
    if not qs:
        return None
    tot = sum(c["end"] - c["start"] for g in qs for c in g["children"]
              if c["name"] in names)
    return tot / len(qs)


def count_per_query(spans: list[dict], cls: str, name: str) -> float | None:
    qs = [g for g in by_query(spans) if g["cls"] == cls]
    if not qs:
        return None
    return sum(1 for g in qs for c in g["children"]
               if c["name"] == name) / len(qs)
