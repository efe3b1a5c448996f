"""Lookups answered over the whole window, from its start to the last
answer. Offered above capacity, this is the rate the server sustains."""

from bench import stats


def read(run):
    specs = run.plan["specs"]
    if not any(specs[r["qid"]]["class"] == "lookup" for r in run.records):
        return None
    return stats.rows_per_second(
        run.records, run.t0,
        lambda r: specs[r["qid"]]["class"] == "lookup")
