"""Host staging of the filter per scan, in ms: ``filter.stage`` spans
(bounds, stack, pad and the puts of columns and bounds)."""

from bench import tracing


def read(run):
    v = tracing.seconds_per_query(run.spans, "scan", ("filter.stage",))
    return None if v is None else 1e3 * v
