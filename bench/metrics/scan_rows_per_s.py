"""Table rows covered by the completed scans over the whole window (from
its start to the last answer)."""

from bench import stats


def read(run):
    specs = run.plan["specs"]
    scans = [r for r in run.records if specs[r["qid"]]["class"] == "scan"]
    if not scans:
        return None
    return stats.rows_per_second(run.records, run.t0,
                                 lambda r: run.rows_total if specs[r["qid"]][
                                     "class"] == "scan" else 0)
