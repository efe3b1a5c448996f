"""The trace reduction: busy union, idle share, module time and the idle
gaps' attribution to host spans, on a small trace."""

import pytest

from bench import devtrace


def trace(ops, modules=(), host=()):
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [list(e) for e in host]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [list(e) for e in modules]},
            {"name": "XLA Ops", "events": [list(e) for e in ops]}]}]}


def test_busy_is_the_union_of_overlapping_ops_inside_the_window():
    t = trace([["a", 0, 100], ["b", 50, 100], ["c", 300, 50],
               ["d", 900, 200]])
    assert devtrace.busy(t, 0, 1000) == [[(0, 150), (300, 350), (900, 1000)]]
    assert devtrace.busy_seconds(t, 0, 1000) == pytest.approx(300e-9)


def test_module_time_and_top_ops():
    t = trace([["fusion", 0, 10], ["copy", 20, 5], ["fusion", 40, 10]],
              modules=[["jit_range_mask_pallas(1)", 0, 12],
                       ["jit_other", 30, 3],
                       ["jit_range_mask_pallas(1)", 40, 11]])
    ev = devtrace.module_events(t, "jit_range_mask_pallas", 0, 100)
    assert sum(e[2] for e in ev) == 23 and len(ev) == 2
    assert devtrace.top_ops(t, 0, 100) == [["fusion", 20e-9], ["copy", 5e-9]]


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = devtrace.idle_gaps([(100, 200), (500, 600)], 0, 1000)
    assert gaps == [(0, 100), (200, 500), (600, 1000)]
    spans = [("serve.query", 0, 800), ("decode.decode", 250, 450)]
    got = dict(map(tuple, devtrace.gaps_by_host_span(gaps, spans)))
    assert got["decode.decode"] == pytest.approx(200e-9)
    assert got["serve.query"] == pytest.approx((100 + 100 + 200) * 1e-9)
    assert got["no host span"] == pytest.approx(200e-9)
    assert sum(got.values()) == pytest.approx(800e-9)


def test_anchor_puts_the_host_clock_on_the_trace_clock():
    t = trace([], host=[["bench.anchor", 12345, 10]])
    assert devtrace.anchor_ns(t) == 12345
    with pytest.raises(LookupError):
        devtrace.anchor_ns(trace([]))


RECORDED = __file__.rsplit("/", 1)[0] + "/data/v5e_range_mask.xplane.pb"


def _brute_busy_ns(tr):
    """Busy nanoseconds by marking every covered nanosecond step of the
    ops' integer-rounded intervals (independent of ``union``)."""
    ops = [e for p in devtrace.device_planes(tr) for ln in p["lines"]
           if ln["name"] == devtrace.OPS_LINE for e in ln["events"]]
    lo = int(min(e[1] for e in ops))
    hi = int(max(e[1] + e[2] for e in ops))
    import numpy as np
    covered = np.zeros(hi - lo + 1, bool)
    for _, s, d in ops:
        covered[int(s) - lo:int(s + d) - lo] = True
    return int(covered.sum())


def test_reduction_of_a_recorded_v5e_trace():
    """20 calls of the 3-column range filter over a 65,536-row group,
    profiled on a TPU v5 lite with an anchor annotation."""
    from bench import roofline
    tr = devtrace.extract(RECORDED)
    assert [p["name"] for p in devtrace.device_planes(tr)] == ["/device:TPU:0"]
    assert devtrace.anchor_ns(tr) == 43769980.0
    ev = devtrace.module_events(tr, "jit_range_mask_pallas", 0, 1e12)
    assert len(ev) == 20
    assert sum(e[2] for e in ev) == 222555.0
    busy_ns = devtrace.busy_seconds(tr, 0, 1e12) * 1e9
    assert busy_ns == pytest.approx(222252.0)
    assert abs(busy_ns - _brute_busy_ns(tr)) <= 140     # 1 ns per op edge
    # the ops lie inside their modules, so busy time is at most module time
    assert busy_ns <= sum(e[2] for e in ev)
    name, secs = devtrace.top_ops(tr, 0, 1e12)[0]
    assert "range_mask_pallas" in name and "tpu_custom_call" in name
    assert secs == pytest.approx(210328e-9)
    need = roofline.least_seconds(20 * roofline.range_mask_bytes(3, 65536),
                                  "TPU v5 lite")
    assert 100 * need / 222555e-9 == pytest.approx(9.3485, abs=1e-3)
    # a window that holds only the first call sees only its time
    first = sorted(ev, key=lambda e: e[1])[0]
    one = devtrace.busy_seconds(tr, first[1], first[1] + first[2]) * 1e9
    assert 0 < one <= first[2]
