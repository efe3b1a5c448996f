"""The one traffic generator: a mix file and a seed in, a request plan out.

A mix (``bench/traffic/<name>.json``) holds only parameters:

  loop          "closed": ``clients`` connections each send their next
                request when the last one returns; "open": requests fall
                due at ``rate_per_s`` on average over ``connections``
                connections, whether or not earlier ones have returned,
                at instants an ``arrivals`` kind sets:
                {"kind": "poisson"} or another ``bench/arrivals/<kind>.py``
                with its parameters
  templates     request shapes, each with an ``op`` (``bench/ops/<op>.py``;
                "query" if absent), a ``class`` (what its latencies count
                as: "scan", "lookup"), ``columns``, a conjunctive ``where``
                whose values may name a parameter (``"$a"``), an optional
                ``head``, a ``share`` of the requests (open loop) and its
                parameters:
                  grid: {"a": [...], ...}  every point of the cartesian
                        product is used equally often, in an order drawn
                        from the seed
                  keys: {"param": {"column": c, "draw": kind, ...}}  values
                        of column ``c`` at the item numbers (rows) that
                        ``bench/draws/<kind>.py`` draws

Every seed gets the same work in another order. An open loop sends
``rate_per_s * seconds`` requests with exact template counts, each
template's requests spread evenly over the window; its arrival instants
are one fixed pattern that the seed turns round the window, and its key
draws are one fixed multiset of item numbers in an order the seed draws.
A closed loop cycles through every grid point in an order the seed draws.
The seed also makes the table, so the keys those item numbers name differ.
"""

from __future__ import annotations

import itertools
import json

import numpy as np

from bench import spec


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    if mix["loop"] == "open" and "arrivals" not in mix:
        raise ValueError(f"{path}: an open loop names its arrivals")
    if not mix.get("templates"):
        raise ValueError(f"{path}: no templates")
    return mix


def _grid_points(tpl: dict) -> list[dict]:
    grid = tpl.get("grid") or {}
    names = sorted(grid)
    return [dict(zip(names, vals))
            for vals in itertools.product(*(grid[k] for k in names))] or [{}]


def bind(tpl: dict, params: dict) -> dict:
    """A template with its ``$name`` values replaced: a request spec."""
    where = [[c, op, params[v[1:]] if isinstance(v, str) and v[:1] == "$"
              else v] for c, op, v in tpl.get("where") or ()]
    return {"op": tpl.get("op", "query"), "columns": list(tpl["columns"]),
            "where": where, "head": tpl.get("head"), "class": tpl["class"],
            "template": tpl["name"]}


def _draw_keys(rng, base, tpl: dict, n: int, column_values,
               bench_dir: str) -> list[dict]:
    """Key parameters: the item numbers are drawn once from ``base`` (the
    same multiset for every seed) and put in an order drawn from ``rng``."""
    out = [dict() for _ in range(n)]
    for name, k in (tpl.get("keys") or {}).items():
        vals = column_values(k["column"])
        draw = spec.plugin("draws", k["draw"], bench_dir)
        items = rng.permutation(draw.items(base, len(vals), n, k))
        for d, v in zip(out, vals[items].tolist()):
            d[name] = v
    return out


def _interleave(counts: np.ndarray, offset: int) -> np.ndarray:
    """Template of each request slot: every template's requests spread
    evenly over the window (each slot goes to the template furthest behind
    its share), the pattern rotated by ``offset``."""
    n = int(counts.sum())
    share = counts / max(n, 1)
    credit = np.zeros(len(counts))
    out = np.empty(n, np.int64)
    for i in range(n):
        credit += share
        t = int(np.argmax(credit))
        out[i] = t
        credit[t] -= 1.0
    return np.roll(out, offset)


def key_columns(mix: dict) -> list[str]:
    """Columns whose values the plan draws keys from."""
    return sorted({k["column"] for t in mix["templates"]
                   for k in (t.get("keys") or {}).values()})


def plan(mix: dict, seed: int, seconds: float, column_values=None,
         bench_dir: str = spec.BENCH_DIR) -> dict:
    """The run's requests. ``column_values(name)`` returns a table column
    for key draws. Closed loop: ``specs`` and per-client ``sequences`` of
    spec indices to cycle. Open loop: ``specs`` (one per request), their
    ``due`` offsets in seconds, sorted."""
    rng = np.random.default_rng(np.random.SeedSequence([seed % (1 << 64),
                                                        0x7A11]))
    base = np.random.default_rng(np.random.SeedSequence([0x7A11]))
    templates = mix["templates"]
    if mix["loop"] == "closed":
        specs, sequences = [], []
        for tpl in templates:
            specs += [bind(tpl, p) for p in _grid_points(tpl)]
        for _ in range(int(mix["clients"])):
            sequences.append(rng.permutation(len(specs)).tolist())
        return {"loop": "closed", "specs": specs, "sequences": sequences,
                "connections": int(mix["clients"])}

    n = int(round(float(mix["rate_per_s"]) * seconds))
    shares = np.asarray([float(t.get("share", 1.0)) for t in templates])
    counts = np.floor(shares / shares.sum() * n).astype(int)
    counts[np.argmax(shares)] += n - counts.sum()
    which = _interleave(counts, int(rng.integers(max(n, 1))))
    specs: list = [None] * n
    for ti, tpl in enumerate(templates):
        slots = np.flatnonzero(which == ti)
        points = _grid_points(tpl)
        order = np.resize(rng.permutation(len(points)), len(slots))
        keys = _draw_keys(rng, base, tpl, len(slots), column_values,
                          bench_dir)
        for slot, pi, kp in zip(slots.tolist(), order.tolist(), keys):
            specs[slot] = bind(tpl, {**points[pi], **kp})
    arrivals = spec.plugin("arrivals", mix["arrivals"]["kind"], bench_dir)
    due = np.sort(arrivals.instants(base, rng, n, seconds, mix["arrivals"]))
    return {"loop": "open", "specs": specs, "due": due.tolist(),
            "connections": int(mix["connections"])}
