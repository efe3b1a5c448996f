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
from repro.obs import querylog, trace
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


def test_filter_round_trip_split_covers_the_kernel_path(table):
    with dataset(table) as ds:
        # the first call imports the kernel and compiles it, before staging
        ds.where(SCAN).select(["id"]).to_table()
        with trace.collect() as tr:
            got = ds.where(SCAN).select(["id"]).to_table()
    assert len(got["id"]) > 0
    filters = [s for s in tr.spans if s.name == "exec.filter"]
    assert len(filters) == N_ROWS // 1024
    for f in filters:
        inside = [s for s in tr.spans if s.name.startswith("filter.")
                  and s.tid == f.tid and f.ts <= s.ts
                  and s.ts + s.dur <= f.ts + f.dur]
        assert sorted(s.name for s in inside) == [
            "filter.fetch", "filter.launch", "filter.stage"]
        assert [s.name for s in sorted(inside, key=lambda s: s.ts)] == [
            "filter.stage", "filter.launch", "filter.fetch"]
        parts = sum(s.dur for s in inside)
        assert 0.8 * f.dur <= parts <= f.dur


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
