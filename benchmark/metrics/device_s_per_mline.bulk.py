"""device_s_per_mline.bulk: engine seconds in the device phase (the
fused step, ops/fused.py over ops/match.py) per million lines."""


def read(run):
    return run.per_mline(run.phase_s("device"))
