"""Find everything a cell needs by the names in ``BENCHMARK.json`` and in
its configuration and mix files.

  configuration     bench/configs/<config>.json
  traffic mix       bench/traffic/<traffic>.json
  metric            bench/metrics/<metric name>.py, ``read(run)``
  column generator  bench/gen/<kind>.py, ``column(ctx, g)`` (a config's
                    ``"gen": {"kind": ...}``)
  key draw          bench/draws/<kind>.py, ``items(rng, n_items, size, k)``
                    (a mix's ``"keys": {...: {"draw": ...}}``)
  arrivals          bench/arrivals/<kind>.py, ``instants(base, rng, n,
                    seconds, a)`` (an open mix's ``"arrivals"``)
  request op        bench/ops/<op>.py, ``columns``, ``warm``, ``send`` and
                    ``expected`` (a template's ``"op"``, else "query")

A later cell, configuration, mix, metric or kind is new files and new
entries; no file here changes.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bm['workloads']]}")


def config_file(name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "configs", f"{name}.json")


def traffic_file(name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "traffic", f"{name}.json")


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(bm: dict, cell_name: str) -> list[dict]:
    return [m for m in bm["end_to_end"] if _applies(m, cell_name)]


def per_layer(bm: dict, cell_name: str) -> list[dict]:
    """Per-layer metrics of the cell: those that list it, and those without
    a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end(bm, cell_name)}
    return [m for m in bm["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


@functools.lru_cache(maxsize=None)
def plugin(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """The module ``bench/<kind>/<name>.py``, loaded once per process."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file {name!r} at {path}")
    mod_name = f"bench_{kind}_" + "".join(
        ch if ch.isalnum() else "_" for ch in name)
    sp = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read`` function of metric ``name``'s reader file."""
    return plugin("metrics", name, bench_dir).read
