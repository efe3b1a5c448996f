"""The served path agrees with the plain NumPy reference on a tiny table
of each configuration, through the whole harness (generator, writer
processes, AF_UNIX server, spawned load generator, digests)."""

import pytest

from bench.tests.helpers import run_tiny


@pytest.mark.parametrize("workload", ["laion-quality-scan",
                                      "criteo-lookup-zipf",
                                      "laion-subset-export"])
def test_cell_is_correct_on_a_tiny_table(workload):
    res = run_tiny(workload)
    assert res["attempted"] > 0
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == ["wrong_answers", "missing_answers",
                                   "failed_requests"]
    assert "setup_s" in res["metrics"]
    assert res["device"]["platform"] == "cpu"


def test_traced_run_reports_its_per_layer_metrics():
    res = run_tiny("criteo-lookup-zipf", trace=1)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert {"plan_ms.lookup", "groups_decoded_per_lookup",
            "decode_ms.lookup", "wire_queue_ms.lookup"} <= set(m)
    # one row group holds each key; bloom sketches prune the others
    assert m["groups_decoded_per_lookup"]["value"] < 2
    assert res["device"]["window_s"] > 0
    assert "idle_gaps" in res["breakdown"]


def test_warm_up_serves_one_request_per_template():
    from bench import run as bench_run
    from bench import spec, traffic
    calls = []
    op = type("Op", (), {"warm": staticmethod(
        lambda server, s: calls.append(s["template"]))})
    for name, templates in (("quality-scan", ["quality"]),
                            ("subset-export", ["export"])):
        calls.clear()
        p = traffic.plan(traffic.load_mix(spec.traffic_file(name)), 7, 51.0)
        assert len(p["specs"]) > 1
        bench_run.warm_up(None, p, {"query": op})
        assert calls == templates
