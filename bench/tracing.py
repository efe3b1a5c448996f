"""Readers of the program's spans and of its profiler annotations.

Spans are the dicts of ``bench/spans.py``. Every span the program records
is also a ``jax.profiler`` annotation of the same name on the trace's host
plane, so an annotation's time is on the trace's host clock, with no
anchor. The device planes keep a clock of their own, off the host's by up
to a millisecond and by a different amount in each run; ``plane_offset_ns``
measures it from the filter's launches. A reader of a span or an
annotation the program does not emit reads ``None``.
"""

from __future__ import annotations

import statistics

from bench import devtrace, spans as _spans

HOST_PLANE = "/host:"
LAUNCH = "filter.launch"
MODULE = "jit_range_mask_pallas"


def of_class(spans: list[dict], name: str, cls: str) -> list[dict]:
    """The spans named ``name`` whose ``tenant`` is the request class."""
    return [s for s in spans
            if s["name"] == name and s["args"].get("tenant") == cls]


def mean_arg(spans: list[dict], name: str, cls: str, arg: str):
    vals = [s["args"][arg] for s in of_class(spans, name, cls)
            if arg in s["args"]]
    return sum(vals) / len(vals) if vals else None


def mean_seconds(spans: list[dict], name: str, cls: str):
    ss = of_class(spans, name, cls)
    return sum(s["end"] - s["start"] for s in ss) / len(ss) if ss else None


def seconds_per_query(spans: list[dict], cls: str, names):
    """``bench.spans.seconds_per_query``, where a span of ``names`` was
    recorded at all."""
    if not any(s["name"] in names for s in spans):
        return None
    return _spans.seconds_per_query(spans, cls, names)


def annotations(tr: dict, names) -> list[tuple[float, float]]:
    """``(start_ns, end_ns)`` of the host planes' events named in
    ``names``."""
    return [(s, s + d) for p in tr["planes"]
            if p["name"].startswith(HOST_PLANE)
            for ln in p["lines"] for n, s, d in ln["events"] if n in names]


def overlap_ns(a, b) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def plane_offset_ns(tr: dict, launch: str = LAUNCH, module: str = MODULE):
    """Device clock minus host clock, in ns, or ``None`` where the trace has
    no ``launch`` annotation or no ``module`` event.

    Each kernel is taken to start as its launch returns, the call having
    queued it. The ``module`` events and the ``launch`` annotations are
    paired in order, at the lag (within the difference of their counts,
    plus one) whose offsets scatter least; the offset is the median, over
    the pairs, of module start minus launch end."""
    ends = sorted(e for _, e in annotations(tr, (launch,)))
    starts = sorted(ev[1] for ev in devtrace.module_events(
        tr, module, float("-inf"), float("inf")))
    if not ends or not starts:
        return None
    best = None
    reach = abs(len(starts) - len(ends)) + 1
    for lag in range(-reach, reach + 1):
        d = [starts[i + lag] - e for i, e in enumerate(ends)
             if 0 <= i + lag < len(starts)]
        if not d:
            continue
        med = statistics.median(d)
        scatter = statistics.median(abs(x - med) for x in d)
        if best is None or scatter < best[0]:
            best = (scatter, med)
    return best[1]


def busy_share(tr: dict, names):
    """% of the union of the annotations named in ``names`` in which the
    device was busy (``devtrace.busy``), averaged over the device planes,
    with the annotations moved onto the device's clock by
    ``plane_offset_ns``."""
    off = plane_offset_ns(tr)
    iv = [] if off is None else [
        (s + off, e + off) for s, e in devtrace.union(annotations(tr, names))]
    per = devtrace.busy(tr, iv[0][0], iv[-1][1]) if iv else []
    if not per:
        return None
    covered = sum(e - s for s, e in iv)
    return 100.0 * sum(overlap_ns(iv, b) for b in per) / len(per) / covered
