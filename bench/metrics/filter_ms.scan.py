"""The filter round trip per scan, in ms: ``exec.filter`` spans (stack the
columns, copy to the device, run the kernel, copy the mask back)."""

from bench import spans


def read(run):
    v = spans.seconds_per_query(run.spans, "scan", ("exec.filter",))
    return None if v is None else 1e3 * v
