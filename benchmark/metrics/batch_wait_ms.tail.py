"""batch_wait_ms.tail: Δ milliseconds answered requests waited in the
micro-batcher's queues (the ``batch_wait`` phase, runtime/batcher.py)
per answered request. None where no request waited in a batch queue."""

from benchmark.stages import PHASE_WALL, per_request_ms


def read(run):
    if not any(n == PHASE_WALL and dict(ls).get("phase") == "batch_wait"
               for n, ls in run.after):
        return None
    return per_request_ms(run, run.phase_s("batch_wait"))
