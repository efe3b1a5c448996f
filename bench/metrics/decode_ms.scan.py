"""Host decode per scan, in ms: ``decode.decode``, ``decode.mask`` and
``decode.dequantize`` spans."""

from bench import spans

DECODE = ("decode.decode", "decode.mask", "decode.dequantize")


def read(run):
    v = spans.seconds_per_query(run.spans, "scan", DECODE)
    return None if v is None else 1e3 * v
