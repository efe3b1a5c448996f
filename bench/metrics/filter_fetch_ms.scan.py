"""The wait for the filter's mask per scan, in ms: ``filter.fetch`` spans
(the kernel's completion, the copy back, slice and cast)."""

from bench import tracing


def read(run):
    v = tracing.seconds_per_query(run.spans, "scan", ("filter.fetch",))
    return None if v is None else 1e3 * v
