"""cache_ms.triage: Δ milliseconds in the line cache per answered
request: the ``cache`` phase (dedup and lookup) plus the
``cache.populate`` stage (runtime/engine.py). None where the program
records no ``cache.populate`` stage, as ``cache_s_per_mline.bulk``."""

from benchmark.stages import per_request_ms, stage_s


def read(run):
    populate = stage_s(run, "cache.populate")
    if populate is None:
        return None
    return per_request_ms(run, run.phase_s("cache") + populate)
