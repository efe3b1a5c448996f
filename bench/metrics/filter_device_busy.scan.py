"""Share of the filter's launch and fetch in which the device was busy, in
%: the union of the ``filter.launch`` and ``filter.fetch`` annotations on
the profiled stretch's host plane, against the device's busy intervals,
both on the trace's own clock."""

from bench import tracing

NAMES = ("filter.launch", "filter.fetch")


def read(run):
    if run.trace is None:
        return None
    return tracing.busy_share(run.trace, NAMES)
