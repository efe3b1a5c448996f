"""Bytes the dataset's readers read (``IOStats.bytes_read`` over the
window) per table row the completed scans covered."""


def read(run):
    specs = run.plan["specs"]
    scans = sum(1 for r in run.records
                if r["ok"] and specs[r["qid"]]["class"] == "scan")
    if not scans:
        return None
    return run.io["bytes_read"] / (scans * run.rows_total)
