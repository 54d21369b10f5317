"""ingest_s_per_mline.bulk: engine seconds in ingest (native/ingest.py:
line split and encode) per million lines."""


def read(run):
    return run.per_mline(run.phase_s("ingest"))
