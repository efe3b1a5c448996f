"""A configuration, a traffic mix, a metric, a column generator, a key
draw, an arrival pattern and a request op added as new files, with new
entries in ``BENCHMARK.json``, run without an edit to any file that is
already there."""

import filecmp
import json
import os
import shutil

from bench import spec
from bench.tests.helpers import run_tiny

NEW_CONFIG = {
    "name": "kv-small", "source": "https://github.com/brianfrankcooper/YCSB",
    "shards": 2, "rows_per_shard": 4096, "rows_per_group": 2048,
    "columns": [
        {"name": "id", "dtype": "int64", "gen": {"kind": "bijection"}},
        {"name": "v", "dtype": "float32", "quant": "bf16",
         "gen": {"kind": "normal", "mean": 0.0, "std": 1.0}}]}
NEW_MIX = {"loop": "closed", "clients": 1, "templates": [
    {"name": "range", "class": "scan", "columns": ["id"],
     "where": [["v", ">=", "$a"]], "grid": {"a": [0.5, 1.5]}}]}
NEW_READER = '''"""Rows served per request."""


def read(run):
    ok = [r for r in run.records if r["ok"]]
    return sum(r["rows"] for r in ok) / len(ok) if ok else None
'''

NEW_GEN = '''"""A float32 ramp: the global row over ``scale``."""

import numpy as np


def column(ctx, g):
    return ((ctx.first + np.arange(ctx.n)) / g["scale"]).astype(ctx.dtype)
'''
NEW_DRAW = '''"""The first ``size`` items, in turn."""

import numpy as np


def items(rng, n_items, size, k):
    return np.arange(size) % n_items
'''
NEW_ARRIVALS = '''"""Evenly spaced instants."""

import numpy as np


def instants(base, rng, n, seconds, a):
    return np.arange(n) * (seconds / n)
'''
NEW_OP = '''"""A query that asks for its first row only."""

from bench.query import digest, predicate, reference_answer


def columns(spec):
    return spec["columns"] + [w[0] for w in spec["where"]]


def warm(server, spec):
    server.query("table", columns=spec["columns"], where=predicate(spec),
                 head=1, tenant=spec["class"])


def send(client, spec):
    res = client.query("table", columns=spec["columns"],
                       where=predicate(spec), head=1, tenant=spec["class"])
    return res.rows, res.wall_seconds, digest(spec["columns"], res.table)


def expected(spec, table, cache):
    return digest(spec["columns"],
                  reference_answer({**spec, "head": 1}, table))
'''
RAMP_CONFIG = {
    "name": "ramp", "source": "https://github.com/brianfrankcooper/YCSB",
    "shards": 2, "rows_per_shard": 4096, "rows_per_group": 2048,
    "columns": [
        {"name": "id", "dtype": "int64", "gen": {"kind": "bijection"}},
        {"name": "v", "dtype": "float32",
         "gen": {"kind": "ramp", "scale": 4096.0}}]}
RAMP_MIX = {"loop": "open", "rate_per_s": 20, "connections": 2,
            "arrivals": {"kind": "even"}, "templates": [
                {"name": "get", "op": "first_row", "class": "lookup",
                 "share": 0.75, "columns": ["id", "v"],
                 "where": [["id", "==", "$k"]],
                 "keys": {"k": {"column": "id", "draw": "first_items"}}},
                {"name": "range", "class": "scan", "share": 0.25,
                 "columns": ["id"], "where": [["v", ">=", "$a"]],
                 "grid": {"a": [0.5, 1.5]}}]}


def _checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def _unchanged(root):
    """Every file the benchmark had is byte for byte what it was."""
    for dirpath, _, files in os.walk(spec.BENCH_DIR):
        if "__pycache__" in dirpath:
            continue
        rel = os.path.relpath(dirpath, spec.BENCH_DIR)
        for f in files:
            assert filecmp.cmp(os.path.join(dirpath, f),
                               root / "bench" / rel / f, shallow=False)


def test_new_files_and_entries_are_enough(tmp_path):
    root = _checkout(tmp_path)
    bm = spec.load()
    bm["configs"].append({"name": "kv-small", "source": NEW_CONFIG["source"],
                          "file": "bench/configs/kv-small.json",
                          "reduced": [], "why": "a test deployment"})
    bm["workloads"].append({"name": "kv-small.range", "config": "kv-small",
                            "traffic": "range-v", "chips": 1,
                            "why": "a test cell"})
    bm["end_to_end"].append({"name": "rows_per_request", "unit": "rows",
                             "better": "higher", "bound": 0.05,
                             "source": "host_clock",
                             "workloads": ["kv-small.range"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    (root / "bench/configs/kv-small.json").write_text(json.dumps(NEW_CONFIG))
    (root / "bench/traffic/range-v.json").write_text(json.dumps(NEW_MIX))
    (root / "bench/metrics/rows_per_request.py").write_text(NEW_READER)

    res = run_tiny("kv-small.range", root=str(root))
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"rows_per_request", "setup_s"}
    assert res["metrics"]["rows_per_request"]["value"] > 0
    _unchanged(root)


def test_new_generator_draw_arrivals_and_op_are_files(tmp_path):
    root = _checkout(tmp_path)
    bm = spec.load()
    bm["configs"].append({"name": "ramp", "source": RAMP_CONFIG["source"],
                          "file": "bench/configs/ramp.json", "reduced": [],
                          "why": "a test deployment"})
    bm["workloads"].append({"name": "ramp.mixed", "config": "ramp",
                            "traffic": "ramp-mixed", "chips": 1,
                            "why": "a test cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    (root / "bench/configs/ramp.json").write_text(json.dumps(RAMP_CONFIG))
    (root / "bench/traffic/ramp-mixed.json").write_text(json.dumps(RAMP_MIX))
    for kind, name, text in (("gen", "ramp", NEW_GEN),
                             ("draws", "first_items", NEW_DRAW),
                             ("arrivals", "even", NEW_ARRIVALS),
                             ("ops", "first_row", NEW_OP)):
        (root / "bench" / kind / f"{name}.py").write_text(text)

    res = run_tiny("ramp.mixed", root=str(root))
    assert res["correct"], res["checks"]
    assert res["attempted"] == 20
    assert set(res["metrics"]) == {"setup_s"}
    _unchanged(root)
