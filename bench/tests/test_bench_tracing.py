"""The readers of the program's own spans and annotations: the service and
wire split, the filter round trip's split, and the device's share of the
filter's launch and fetch, on span dicts and on a small trace; and the
order of a served query's spans that ``bench.spans.by_query`` relies on."""

import types

import numpy as np
import pytest

from bench import spec, spans, tracing
from bench.tests.test_bench_devtrace import trace


def span(name, start, end, tid=1, **args):
    return {"name": name, "start": start, "end": end, "tid": tid,
            "args": args}


# two scans on two pool threads and one lookup; the first scan missed the
# plan cache, so its plan span comes before its ``serve.query``
SPANS = [
    span("plan.lower", 0.0, 0.5),
    span("serve.query", 1.0, 2.0, tenant="scan", queued_ms=4.0),
    span("filter.stage", 1.1, 1.2),
    span("filter.launch", 1.2, 1.25),
    span("filter.fetch", 1.25, 1.5),
    span("filter.stage", 1.6, 1.7),
    span("filter.fetch", 1.75, 1.8),
    span("serve.query", 1.0, 1.4, tid=2, tenant="scan", queued_ms=1.0),
    span("filter.stage", 1.1, 1.3, tid=2),
    span("serve.query", 3.0, 3.1, tenant="lookup", queued_ms=100.0),
    span("filter.stage", 3.0, 3.05),
    span("serve.respond", 2.0, 2.01, tid=9, tenant="scan"),
    span("serve.respond", 1.4, 1.43, tid=8, tenant="scan"),
    span("serve.respond", 3.1, 3.5, tid=9, tenant="lookup"),
]


def read(name, run):
    return spec.reader(name)(run)


def test_service_and_wire_readers_take_the_scans_means():
    run = types.SimpleNamespace(spans=SPANS, trace=None)
    assert read("pool_wait_ms.scan", run) == pytest.approx(2.5)
    assert read("respond_ms.scan", run) == pytest.approx(20.0)


def test_filter_readers_sum_their_spans_per_scan():
    run = types.SimpleNamespace(spans=SPANS, trace=None)
    # (0.1 + 0.1) on the first scan, 0.2 on the second
    assert read("filter_stage_ms.scan", run) == pytest.approx(200.0)
    assert read("filter_fetch_ms.scan", run) == pytest.approx(150.0)


def test_span_readers_read_nothing_where_the_program_records_no_span():
    older = [{**s, "args": {k: v for k, v in s["args"].items()
                            if k != "queued_ms"}}
             for s in SPANS
             if not s["name"].startswith(("filter.", "serve.respond"))]
    run = types.SimpleNamespace(spans=older, trace=None)
    for name in ("pool_wait_ms.scan", "respond_ms.scan",
                 "filter_stage_ms.scan", "filter_fetch_ms.scan",
                 "filter_device_busy.scan"):
        assert read(name, run) is None, name


KERNEL = "jit_range_mask_pallas(1)"


def test_device_busy_share_of_the_filter_annotations():
    host = [["filter.launch", 100, 50], ["filter.fetch", 150, 250],
            ["filter.stage", 0, 100], ["decode.decode", 500, 300]]
    # the kernel starts as its launch returns; busy 120-160 and 380-450 and
    # 600-700: 40 + 20 inside 100-400
    t = trace([["k", 120, 40], ["c", 380, 70], ["d", 600, 100]],
              modules=[[KERNEL, 150, 10]], host=host)
    run = types.SimpleNamespace(spans=[], trace=t)
    assert read("filter_device_busy.scan", run) == pytest.approx(
        100 * 60 / 300)


def launches(n, offset, jitter=()):
    """``n`` filter round trips 14 to 26 us apart on the host clock (stage
    2, launch 2, fetch 4 us), and their kernels (1 us, starting as the
    launch returns, plus ``jitter``) on a device clock ``offset`` ns
    ahead."""
    host, ops = [], []
    for i in range(n):
        t = 20_000 * i + 2_000 * (i * i % 7)
        host += [["filter.stage", t, 2_000], ["filter.launch", t + 2_000,
                                              2_000],
                 ["filter.fetch", t + 4_000, 4_000]]
        j = jitter[i] if i < len(jitter) else 0
        ops.append([KERNEL, t + 4_000 + offset + j, 1_000])
    return host, ops


@pytest.mark.parametrize("offset", [-1_090_000, 0, 110_000])
def test_device_busy_share_puts_the_annotations_on_the_device_clock(offset):
    host, ops = launches(40, offset, jitter=[300, -300, 150, -150] * 10)
    # a kernel whose launch began before the profiler: no annotation
    ops.insert(0, [KERNEL, -20_000 + 4_000 + offset, 1_000])
    t = trace(ops, modules=ops, host=host)
    assert tracing.plane_offset_ns(t) == pytest.approx(offset, abs=300)
    # every kernel inside its launch and fetch: 1 us of each 6 us
    assert read("filter_device_busy.scan", types.SimpleNamespace(
        spans=[], trace=t)) == pytest.approx(100 / 6)


def test_device_busy_share_reads_nothing_without_the_kernel_module():
    host, ops = launches(4, 0)
    t = trace(ops, host=host)
    assert tracing.plane_offset_ns(t) is None
    assert read("filter_device_busy.scan",
                types.SimpleNamespace(spans=[], trace=t)) is None


def test_device_busy_share_merges_overlapping_threads_and_averages_planes():
    t = trace([["k", 0, 100]], modules=[[KERNEL, 300, 10]],
              host=[["filter.fetch", 0, 200], ["filter.launch", 50, 250]])
    t["planes"][0]["lines"].append(
        {"name": "other thread", "events": [["filter.fetch", 400, 100]]})
    t["planes"].append({"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["k", 200, 300]]}]})
    # union 0-300 and 400-500: plane 0 busy 100 of it, plane 1 busy 200
    assert tracing.busy_share(t, ("filter.fetch", "filter.launch")) == \
        pytest.approx(100 * (100 + 200) / 2 / 400)


def test_device_busy_share_reads_nothing_without_an_annotation():
    t = trace([["k", 0, 100]], host=[["bench.anchor", 0, 10],
                                     ["filter.stage", 0, 50]])
    assert read("filter_device_busy.scan",
                types.SimpleNamespace(spans=[], trace=t)) is None
    assert read("filter_device_busy.scan",
                types.SimpleNamespace(spans=[], trace=None)) is None


def test_overlap_of_two_interval_lists():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (28, 45)]
    assert tracing.overlap_ns(a, b) == 5 + 5 + 2 + 5
    assert tracing.overlap_ns(a, []) == 0


def test_plan_spans_of_a_served_query_precede_it_on_its_thread(tmp_path):
    """A plan-cache miss plans on the pool thread inside the query's own
    scope, and still before ``serve.query`` opens: under a process-wide
    tracer ``by_query`` gives each query its plan spans."""
    from bench import run as bench_run
    from repro.core import BullionWriter, ColumnSpec
    from repro.obs import trace as program_trace
    from repro.scan import C
    from repro.serve import DatasetServer

    d = tmp_path / "t"
    d.mkdir()
    w = BullionWriter(str(d / "part-000.bln"),
                      [ColumnSpec("id", "int64"),
                       ColumnSpec("score", "float32")],
                      rows_per_group=256)
    w.write_table({"id": np.arange(1024, dtype=np.int64),
                   "score": np.linspace(0, 1, 1024, dtype=np.float32)})
    w.close()
    prev = program_trace.current()
    tracer = program_trace.enable()
    try:
        with DatasetServer({"t": str(d)}, max_workers=2) as server:
            for lo in (0.25, 0.5, 0.25):
                server.query("t", columns=["id"], where=C("score") >= lo,
                             tenant="scan")
    finally:
        program_trace.install(prev)
    ss = bench_run.span_dicts(tracer)
    queries = [s for s in ss if s["name"] == "serve.query"]
    plans = [s for s in ss if s["name"].startswith(spans.PLAN_PREFIXES)]
    assert [q["args"]["cache_hit"] for q in queries] == [False, False, True]
    assert {"plan.optimize", "plan.lower"} <= {p["name"] for p in plans}
    for p in plans:
        assert any(q["tid"] == p["tid"] and q["start"] >= p["end"]
                   for q in queries), p["name"]
    groups = spans.by_query(ss)
    assert sum(len([c for c in g["children"] if c in plans])
               for g in groups) == len(plans)
    by_hit = {}
    for g in groups:
        names = {c["name"] for c in g["children"]}
        by_hit.setdefault(g["query"]["args"]["cache_hit"], []).append(names)
    assert all("plan.lower" in n for n in by_hit[False])
    assert all("plan.lower" not in n for n in by_hit[True])
    assert all("filter.launch" in n for ns in by_hit.values() for n in ns)


def test_traced_scan_reports_the_round_trip_and_wire_split():
    from bench.tests.helpers import run_tiny
    res = run_tiny("laion-quality-scan", trace=1)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert {"pool_wait_ms.scan", "respond_ms.scan", "filter_stage_ms.scan",
            "filter_fetch_ms.scan"} <= set(m)
    assert m["pool_wait_ms.scan"] >= 0 and m["respond_ms.scan"] > 0
    assert 0 < m["filter_stage_ms.scan"] + m["filter_fetch_ms.scan"] \
        <= m["filter_ms.scan"]
    # the CPU's trace has no TPU plane to be busy
    assert "filter_device_busy.scan" not in m
