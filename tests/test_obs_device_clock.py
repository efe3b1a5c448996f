"""Spans on the profiler's clock, and the spans that split the served
scan: the filter's device round trip, the wait for a pool worker, the
answer on the session thread, and plan spans inside the query's scope."""

import glob
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.core import BullionWriter, ColumnSpec
from repro.dataset import clear_footer_cache, dataset
from repro.obs import metrics, querylog, trace
from repro.scan import C
from repro.serve import DatasetServer, ServeClient

N_ROWS = 4096


@pytest.fixture(autouse=True)
def _isolate_tracer():
    """CI runs the suite under BULLION_TRACE; keep installs from leaking."""
    prev = trace.current()
    yield
    trace.install(prev)


@pytest.fixture
def table(tmp_path):
    clear_footer_cache()
    d = tmp_path / "t"
    d.mkdir()
    rng = np.random.default_rng(0)
    w = BullionWriter(str(d / "part-0000.bln"),
                      [ColumnSpec("id", "int64"),
                       ColumnSpec("score", "float32"),
                       ColumnSpec("risk", "float32")],
                      rows_per_group=1024, page_rows=256)
    w.write_table({"id": np.arange(N_ROWS, dtype=np.int64),
                   "score": rng.random(N_ROWS).astype(np.float32),
                   "risk": rng.random(N_ROWS).astype(np.float32)})
    w.close()
    return str(d)


SCAN = (C("score") >= 0.25) & (C("risk") <= 0.75)


def _host_events(prof_dir: str, name: str) -> list:
    import jax
    (path,) = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [ev for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name == name]


def test_span_is_a_profiler_annotation_on_the_host_plane(tmp_path):
    import jax
    prof_dir = str(tmp_path / "prof")
    with trace.collect() as tr:
        jax.profiler.start_trace(prof_dir)
        try:
            with trace.span("unit.device_clock", cat="test", rows=3):
                time.sleep(0.05)
        finally:
            jax.profiler.stop_trace()
    (rec,) = tr.spans
    (ev,) = _host_events(prof_dir, "unit.device_clock")
    assert ev.duration_ns / 1e9 == pytest.approx(rec.dur, rel=0.1)
    assert rec.args == {"rows": 3}


def test_repro_obs_imports_no_jax():
    code = ("import sys; import repro.obs; from repro.obs import trace; "
            "tr = trace.enable()\n"
            "with trace.span('x'): pass\n"
            "assert len(tr.spans) == 1\n"
            "print('jax' in sys.modules)")
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + [p for p in [os.environ.get("PYTHONPATH")] if p])}
    env.pop("BULLION_TRACE", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def test_served_scan_with_tracing_off_allocates_no_spans(table):
    trace.install(None)
    with DatasetServer({"t": table}) as srv:
        if srv.query_log.slow_seconds is not None:
            pytest.skip("BULLION_SLOW_MS set in this environment")
        path = srv.serve()
        with ServeClient(path) as cli:
            cli.query("t", columns=["id"], where=SCAN, tenant="scan")
            before = trace.allocations()
            res = cli.query("t", columns=["id"], where=SCAN, tenant="scan")
        assert res.cache_hit and res.rows > 0
        assert trace.allocations() == before


def _lineitem(path: str, mixed_widths: bool) -> str:
    """Two groups of 8,192 int32 rows in pages of 4,096, every page
    bit-packed. With ``mixed_widths`` each group's first page of ``qty``
    fits 5 bits and its other needs 6: the packed path refuses the group
    and the kernel reads it decoded."""
    rng = np.random.default_rng(17)
    n = 16_384
    qty = rng.integers(1, 51, n).astype(np.int32)
    if mixed_widths:
        for g in range(0, n, 8192):
            qty[g:g + 4096] %= 31
    w = BullionWriter(path, [ColumnSpec(c, "int32")
                             for c in ("ship", "disc", "qty", "price")],
                      rows_per_group=8192, page_rows=4096)
    w.write_table({"ship": rng.integers(8036, 10_592, n).astype(np.int32),
                   "disc": rng.integers(0, 11, n).astype(np.int32),
                   "qty": qty,
                   "price": qty * rng.integers(900, 2100, n).astype(np.int32)})
    w.close()
    return path


Q6 = (C("ship") >= 8766) & (C("disc") >= 5) & (C("qty") < 24)

# path -> (kernel layer, enclosing span, groups, mixed widths)
ROUND_TRIPS = {
    "filter": ("filter", "exec.filter", N_ROWS // 1024, None),
    "aggregate_decoded": ("aggregate", "exec.aggregate", 2, True),
    "aggregate_packed": ("aggregate", "exec.aggregate", 2, False),
}
COUNTERS = ("bullion.filter.kernel_calls", "bullion.aggregate.kernel_calls",
            "bullion.aggregate.packed_groups", "bullion.aggregate.host_groups")


@pytest.mark.parametrize("path", sorted(ROUND_TRIPS))
def test_filter_round_trip_split_covers_the_kernel_path(table, tmp_path,
                                                        path):
    layer, outer, groups, mixed_widths = ROUND_TRIPS[path]
    if mixed_widths is None:
        src = table

        def query(ds):
            return len(ds.where(SCAN).select(["id"]).to_table()["id"])
    else:
        src = _lineitem(str(tmp_path / "lineitem.bln"), mixed_widths)

        def query(ds):
            return ds.where(Q6).aggregate(sum_product=("price", "disc")).rows
    with dataset(src) as ds:
        # the first call imports the kernel and compiles it, before staging
        query(ds)
        before = [metrics.counter(c).value for c in COUNTERS]
        with trace.collect() as tr:
            got = query(ds)
        moved = {c: metrics.counter(c).value - b
                 for c, b in zip(COUNTERS, before)}
    assert got > 0
    spans = [s for s in tr.spans if s.name == outer]
    assert len(spans) == groups
    for f in spans:
        inside = [s for s in tr.spans if s.name.startswith(layer + ".")
                  and s.tid == f.tid and f.ts <= s.ts
                  and s.ts + s.dur <= f.ts + f.dur]
        assert [s.name for s in sorted(inside, key=lambda s: s.ts)] == [
            f"{layer}.stage", f"{layer}.launch", f"{layer}.fetch"]
        parts = sum(s.dur for s in inside)
        assert 0.8 * f.dur <= parts <= f.dur
    packed = 0 if mixed_widths is not False else groups
    assert moved == {
        "bullion.filter.kernel_calls": groups if layer == "filter" else 0,
        "bullion.aggregate.kernel_calls": groups if layer == "aggregate"
        else 0,
        "bullion.aggregate.packed_groups": packed,
        "bullion.aggregate.host_groups": 0}


def test_numpy_filter_path_has_no_round_trip_spans(table):
    with dataset(table) as ds:
        with trace.collect() as tr:
            ds.where(C("id").isin([3, 4000])).select(["id"]).to_table()
    assert any(s.name == "exec.filter" for s in tr.spans)
    assert not any(s.name.startswith("filter.") for s in tr.spans)


def test_served_query_times_its_pool_wait_and_its_answer(table):
    with DatasetServer({"t": table}) as srv:
        path = srv.serve()
        with trace.collect() as tr:
            with ServeClient(path) as cli:
                cli.query("t", columns=["id"], where=SCAN, tenant="scan")
    (q,) = [s for s in tr.spans if s.name == "serve.query"]
    (r,) = [s for s in tr.spans if s.name == "serve.respond"]
    assert q.args["queued_ms"] >= 0 and q.args["tenant"] == "scan"
    assert set(r.args) == {"tenant", "encode_ms"} and r.args["tenant"] == "scan"
    # the encode is the server's own part of the answer; the rest is the send
    assert 0 <= r.args["encode_ms"] <= 1e3 * r.dur
    # the answer is timed on the session thread, after the query
    assert r.tid != q.tid and r.ts >= q.ts + q.dur


def test_plan_spans_of_a_cache_miss_reach_the_query_record(table):
    log = querylog.QueryLog(slow_seconds=0.0)
    with DatasetServer({"t": table}, query_log=log) as srv:
        srv.query("t", columns=["id"], where=SCAN)
        srv.query("t", columns=["id"], where=SCAN)
    miss, hit = srv.query_log.records()
    assert not miss.cache_hit and hit.cache_hit
    assert {"plan.optimize", "plan.lower"} <= set(miss.stages)
    assert "plan.lower" in {s["name"] for s in miss.spans}
    assert "plan.lower" not in hit.stages


def test_decode_spans_name_their_encoding_and_the_histograms_are_gone(
        table):
    with DatasetServer({"t": table}) as srv:
        with trace.collect() as tr:
            srv.query("t", columns=["id", "score"], where=SCAN)
        text = srv.metrics_text()
    decodes = [s for s in tr.spans if s.name == "decode.decode"]
    assert decodes and {s.args["encoding"] for s in decodes} == {"scalar"}
    assert "page_seconds" not in text and "pread_seconds" not in text


def test_a_chunk_of_mixed_pages_names_every_family():
    from repro.dataset.executor import _encoding
    flags = np.array([0, 2, 0x80 | 0, 99], np.uint8)
    assert _encoding(flags, [0, 2]) == "scalar"
    assert _encoding(flags, [0, 1, 3]) == "scalar+string+type99"
