"""Mean wait of a scan for a pool worker, in ms: the ``queued_ms`` of the
scans' ``serve.query`` spans, from the server's submit to the start on a
pool thread (time that ``wall_seconds`` leaves out)."""

from bench import tracing


def read(run):
    return tracing.mean_arg(run.spans, "serve.query", "scan", "queued_ms")
