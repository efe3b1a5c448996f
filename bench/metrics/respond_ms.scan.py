"""Mean time the server takes to answer a scan once its result is in hand,
in ms: ``serve.respond`` spans (table encode, JSON and send)."""

from bench import tracing


def read(run):
    v = tracing.mean_seconds(run.spans, "serve.respond", "scan")
    return None if v is None else 1e3 * v
