"""finish_s_per_mline.bulk: engine seconds in extract, finalize and
assemble (runtime/finalize.py, the engine's _finish) per million lines."""


def read(run):
    return run.per_mline(run.phase_s("extract", "finalize", "assemble"))
