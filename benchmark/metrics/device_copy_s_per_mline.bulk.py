"""device_copy_s_per_mline.bulk: Δ seconds in the device step's
``device.upload`` (host → device) and ``device.readback`` (device → host)
stages (ops/fused.py) per million lines answered."""

from benchmark.stages import stage_s


def read(run):
    s = stage_s(run, "device.upload", "device.readback")
    return run.per_mline(s) if s is not None else None
