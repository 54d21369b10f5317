"""p95_ms: 95th percentile latency of every request due in the window,
timed from its due time (nearest rank)."""

from benchmark.measure import nearest_rank


def read(run):
    lat = run.latencies_ms()
    return nearest_rank(lat, 95) if lat else None
