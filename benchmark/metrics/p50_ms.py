"""p50_ms: median latency of every request due in the window, timed
from its due time (nearest rank)."""

from benchmark.measure import nearest_rank


def read(run):
    lat = run.latencies_ms()
    return nearest_rank(lat, 50) if lat else None
