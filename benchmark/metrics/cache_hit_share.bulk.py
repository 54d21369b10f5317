"""cache_hit_share.bulk: line-weighted line-cache hits ÷ (hits + misses)
over the window (runtime/linecache.py counters on /metrics)."""


def read(run):
    hits = run.delta("logparser_line_cache_hits_total")
    misses = run.delta("logparser_line_cache_misses_total")
    if hits + misses <= 0:
        return None
    return hits / (hits + misses)
