"""device_wait_s_per_mline.bulk: Δ seconds in the device step's
``device.wait`` stage (``block_until_ready`` on the program's output,
ops/fused.py) per million lines answered."""

from benchmark.stages import stage_s


def read(run):
    s = stage_s(run, "device.wait")
    return run.per_mline(s) if s is not None else None
