"""Shared pieces of the benchmark's CPU tests: a cell run at a size a test
run holds, with the look for a chip skipped."""

from __future__ import annotations

import argparse

from bench import spec

TINY = {"shards": 2, "rows_per_shard": 4096, "rows_per_group": 2048}
TINY_RATES = {"criteo-lookup-zipf": {"rate_per_s": 40}}

# The YCSB-C feature-lookup cell as entries only: its configuration, mix
# and readers are files of the benchmark, but a traced run of it holds no
# device operation, so BENCHMARK.json does not name it yet.
LOOKUP = {
    "config": {"name": "criteo-features",
               "source": "https://github.com/mlcommons/training",
               "file": "bench/configs/criteo-features.json",
               "reduced": ["shards", "rows_per_shard"], "why": "lookups"},
    "cell": {"name": "criteo-lookup-zipf", "config": "criteo-features",
             "traffic": "lookup-zipf", "chips": 1, "why": "YCSB-C"},
    "end_to_end": [{"name": "lookups_per_s", "unit": "lookups/s",
                    "better": "higher", "bound": 0.2, "source": "host_clock",
                    "workloads": ["criteo-lookup-zipf"]}],
    "per_layer": [{"name": n, "unit": u, "better": "lower",
                   "source": "program_span", "layer": "lookup",
                   "moves": "lookups_per_s",
                   "workloads": ["criteo-lookup-zipf"]}
                  for n, u in (("wire_queue_ms.lookup", "ms"),
                               ("plan_ms.lookup", "ms"),
                               ("groups_decoded_per_lookup", "count"),
                               ("decode_ms.lookup", "ms"))],
}


def benchmark_with_lookup() -> dict:
    """``BENCHMARK.json`` with the lookup cell's entries added."""
    bm = spec.load()
    bm["configs"].append(LOOKUP["config"])
    bm["workloads"].append(LOOKUP["cell"])
    bm["end_to_end"] += LOOKUP["end_to_end"]
    bm["per_layer"] += LOOKUP["per_layer"]
    return bm


def run_tiny(workload: str, *, seed: int = 2**31 + 11, seconds: float = 1.0,
             trace: int = 0, **kw) -> dict:
    import jax

    from bench import run as bench_run
    if "root" not in kw:
        kw.setdefault("bm", benchmark_with_lookup())
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    return bench_run.run_cell(args, devices=jax.devices(), sizes=TINY,
                              rates=TINY_RATES.get(workload), **kw)
