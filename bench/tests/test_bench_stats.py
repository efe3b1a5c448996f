"""Rate and percentile arithmetic: every request of the window counts,
timed from its due time, and a failed request counts as attempted."""

import math

import pytest

from bench import stats


def rec(qid, due, sent, done, ok=True):
    return {"qid": qid, "due": due, "sent": sent, "done": done, "ok": ok}


SPECS = [{"class": "lookup"}, {"class": "probe"}]


def test_latency_runs_from_due_time_not_send_time():
    r = [rec(0, 10.0, 10.5, 10.6)]        # sent 0.5 s late
    assert stats.latencies(r) == [pytest.approx(0.6)]


def test_failed_requests_count_and_raise_percentiles():
    rs = [rec(0, 0.0, 0.0, 0.01 * (i + 1)) for i in range(19)]
    rs.append(rec(0, 0.0, 0.0, 0.001, ok=False))
    lat = stats.latencies(rs)
    assert len(lat) == 20 and math.isinf(max(lat))
    assert stats.percentile(lat, 95) == pytest.approx(0.19)
    assert math.isinf(stats.percentile(lat, 100))


def test_percentile_is_nearest_rank_over_all_values():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_latencies_of_one_class_only():
    rs = [rec(0, 0, 0, 1.0), rec(1, 0, 0, 2.0), rec(0, 0, 0, 3.0)]
    assert stats.latencies(rs, "lookup", SPECS) == [1.0, 3.0]


def test_rate_takes_all_work_over_the_whole_window():
    t0 = 100.0
    rs = [rec(0, t0, t0, t0 + 1.0), rec(0, t0 + 1, t0 + 1, t0 + 4.0),
          rec(0, t0, t0, t0 + 2.0, ok=False)]
    # the window runs to the last answer, failed or not; only answers count
    assert stats.window(rs, t0) == (t0, t0 + 4.0)
    assert stats.rows_per_second(rs, t0, lambda r: 1000) == \
        pytest.approx(2000 / 4.0)

