"""The aggregate round trip per scan, in ms: ``exec.aggregate`` spans (the
partial aggregate of each row group once decoded: stage the columns, run
the fused filter-and-sum kernel, fetch and add its partial sums; or the
NumPy path)."""

from bench import tracing


def read(run):
    v = tracing.seconds_per_query(run.spans, "scan", ("exec.aggregate",))
    return None if v is None else 1e3 * v
