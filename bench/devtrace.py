"""From a profiler trace to device numbers.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
lists: ``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, dur_ns], ...]}]}]}``. Everything else works on that form, so a
small recorded trace can be kept as JSON and the reduction checked on it.

Device time is read from each TPU plane's "XLA Ops" line: busy time is the
union of its op intervals inside the traced window, averaged over the
chips used. A compiled module's time is the sum of its events on the
"XLA Modules" line. Host spans are put on the trace's clock by an anchor:
a ``TraceAnnotation`` named ``ANCHOR`` opened at a recorded
``time.perf_counter`` instant.
"""

from __future__ import annotations

import glob
import os

ANCHOR = "bench.anchor"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def extract(path: str) -> dict:
    """The ``.xplane.pb`` at ``path`` (or the newest under a directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(tr: dict) -> list[dict]:
    return [p for p in tr["planes"]
            if p["name"].startswith("/device:TPU:")
            and any(ln["name"] == OPS_LINE for ln in p["lines"])]


def _line(plane: dict, name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy(tr: dict, lo_ns: float, hi_ns: float) -> list[list]:
    """Per device plane, the merged busy intervals inside the window."""
    return [clip(union((ev[1], ev[1] + ev[2]) for ev in _line(p, OPS_LINE)),
                 lo_ns, hi_ns) for p in device_planes(tr)]


def busy_seconds(tr: dict, lo_ns: float, hi_ns: float) -> float:
    """Seconds in which an op ran, averaged over the device planes."""
    per = busy(tr, lo_ns, hi_ns)
    if not per:
        return 0.0
    return sum(sum(e - s for s, e in iv) for iv in per) / len(per) / 1e9


def module_events(tr: dict, prefix: str, lo_ns: float, hi_ns: float) -> list:
    """Events of compiled modules whose name starts with ``prefix``, inside
    the window, over every device plane."""
    return [ev for p in device_planes(tr) for ev in _line(p, MODULES_LINE)
            if ev[0].startswith(prefix) and lo_ns <= ev[1] < hi_ns]


def top_ops(tr: dict, lo_ns: float, hi_ns: float, k: int = 10) -> list:
    """``[name, seconds]`` of the ``k`` ops that took the most device time,
    summed over the window and the device planes."""
    tot: dict = {}
    for p in device_planes(tr):
        for name, s, d in _line(p, OPS_LINE):
            if lo_ns <= s < hi_ns:
                tot[name] = tot.get(name, 0.0) + d / 1e9
    return [[n, v] for n, v in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def anchor_ns(tr: dict) -> float:
    """Trace-clock instant of the anchor annotation."""
    for p in tr["planes"]:
        for ln in p["lines"]:
            for name, s, _ in ln["events"]:
                if name == ANCHOR:
                    return s
    raise LookupError(f"no {ANCHOR!r} event in the trace")


def idle_gaps(busy_iv, lo_ns: float, hi_ns: float) -> list[tuple]:
    """The gaps between busy intervals inside the window."""
    gaps, cur = [], lo_ns
    for s, e in busy_iv:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi_ns > cur:
        gaps.append((cur, hi_ns))
    return gaps


def gaps_by_host_span(gaps, spans, k: int = 10) -> list:
    """``[label, seconds]``: idle device time attributed to the innermost
    host span (``(name, start_ns, end_ns)`` on the trace clock) that covers
    each stretch of a gap; what no span covers is "no host span"."""
    gaps = sorted(gaps)
    if not gaps:
        return []
    # sweep the window's elementary segments with the set of open spans
    marks = sorted([(s, 1, i) for i, (_, s, _) in enumerate(spans)]
                   + [(e, 0, i) for i, (_, _, e) in enumerate(spans)]
                   + [(gaps[-1][1], 0, -1)])
    tot: dict = {}
    active: set = set()
    prev, gi = gaps[0][0], 0
    for t, opening, i in marks:
        if t > prev:
            while gi < len(gaps) and gaps[gi][1] <= prev:
                gi += 1
            over, j = 0.0, gi
            while j < len(gaps) and gaps[j][0] < t:
                over += max(0.0, min(t, gaps[j][1]) - max(prev, gaps[j][0]))
                j += 1
            if over > 0:
                inner = min(active, key=lambda a: spans[a][2] - spans[a][1],
                            default=None)
                label = spans[inner][0] if inner is not None \
                    else "no host span"
                tot[label] = tot.get(label, 0.0) + over / 1e9
            prev = t
        if i < 0:
            continue
        if opening:
            active.add(i)
        else:
            active.discard(i)
    return [[n, v] for n, v in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]
