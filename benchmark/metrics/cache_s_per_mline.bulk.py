"""cache_s_per_mline.bulk: Δ seconds in the line cache per million lines
answered: the ``cache`` phase (dedup and lookup) plus the
``cache.populate`` stage (the store of the residual's rows, between the
``device`` and ``extract`` phases; runtime/engine.py). None where the
program records no ``cache.populate`` stage: there the populate runs
outside every span, and a figure without it would not be the same sum."""

from benchmark.stages import stage_s


def read(run):
    populate = stage_s(run, "cache.populate")
    if populate is None:
        return None
    return run.per_mline(run.phase_s("cache") + populate)
