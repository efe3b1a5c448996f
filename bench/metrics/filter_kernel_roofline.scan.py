"""The range-filter kernel's share of its roofline, in %: the least time
the HBM traffic of its calls needs at the chip's published bandwidth, over
the device time of every ``jit_range_mask_pallas`` module in the profiled
stretch. The bytes come from the call's shape, which a cell's mix fixes: a
template whose ``where`` is all ranges over float32 columns runs the
kernel once per row group, on those columns and the group's rows. A cell
whose mix makes calls of several shapes reads nothing."""

from bench import devtrace, roofline

MODULE = "jit_range_mask_pallas"


def kernel_shapes(cfg: dict, mix: dict) -> set:
    """(columns, rows) of every range-filter call the mix makes."""
    dtypes = {c["name"]: c["dtype"] for c in cfg["columns"]}
    out = set()
    for t in mix["templates"]:
        where = t.get("where") or []
        cols = {c for c, op, _ in where}
        if where and all(op != "==" for _, op, _ in where) and all(
                dtypes[c] == "float32" for c in cols):
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
        len(events) * roofline.range_mask_bytes(columns, rows),
        run.device_kind)
    took = sum(ev[2] for ev in events) / 1e9
    return 100.0 * need / took
