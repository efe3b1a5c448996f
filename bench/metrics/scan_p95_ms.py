"""95th percentile of every scan's latency in the window, in ms."""

from bench import stats


def read(run):
    lat = stats.latencies(run.records, "scan", run.plan["specs"])
    return 1e3 * stats.percentile(lat, 95) if lat else None
