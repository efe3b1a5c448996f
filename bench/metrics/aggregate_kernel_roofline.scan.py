"""The fused filter-and-sum kernel's share of its roofline, in %: the least
time the HBM traffic of its calls needs at the chip's published bandwidth,
over the device time of every ``jit_sum_product_pallas`` module in the
profiled stretch. The bytes come from the call's shape, which a cell's mix
fixes: a ``sum_product`` template runs the kernel once per row group, on
the distinct columns of its ``where`` and its two factors, and the group's
rows. A cell whose mix makes calls of several shapes reads nothing."""

from bench import devtrace, roofline, roofline_sum_product

MODULE = "jit_sum_product_pallas"


def kernel_shapes(cfg: dict, mix: dict) -> set:
    """(columns, rows) of every kernel call the mix makes."""
    out = set()
    for t in mix["templates"]:
        if t.get("op") == "sum_product":
            cols = {c for c, _, _ in t.get("where") or []} | set(t["columns"])
            out.add((len(cols), int(cfg["rows_per_group"])))
    return out


def read(run):
    shapes = kernel_shapes(run.cfg, run.mix)
    if run.trace is None or len(shapes) != 1:
        return None
    events = devtrace.module_events(run.trace, MODULE, *run.trace_window_ns)
    if not events:
        return None
    (columns, rows), = shapes
    need = roofline.least_seconds(
        len(events) * roofline_sum_product.sum_product_bytes(columns, rows),
        run.device_kind)
    took = sum(ev[2] for ev in events) / 1e9
    return 100.0 * need / took
