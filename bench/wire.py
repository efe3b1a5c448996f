"""What a request spends outside the server's own ``wall_seconds``."""

from __future__ import annotations

from bench import stats


def median_outside_server_ms(run, cls: str):
    specs = run.plan["specs"]
    gaps = [(r["done"] - r["sent"] - r["wall"]) for r in run.records
            if r["ok"] and specs[r["qid"]]["class"] == cls]
    return 1e3 * stats.percentile(gaps, 50) if gaps else None
