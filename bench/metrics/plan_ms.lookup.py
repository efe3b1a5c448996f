"""Planning per lookup, in ms: the ``plan.optimize`` and ``plan.lower``
spans (``scan.plan``, zone maps and bloom sketches, nests in the latter)
that a plan-cache miss runs, over every lookup served."""

from bench import spans


def read(run):
    v = spans.seconds_per_query(run.spans, "lookup",
                                ("plan.optimize", "plan.lower"))
    return None if v is None else 1e3 * v
