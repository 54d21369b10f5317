"""flush_ms.tail: Δ milliseconds of the micro-batcher's flushes, the
shared device step and the serial demux of its members
(``logparser_batch_flush_seconds``, both parts) per flush (the device
part's count: one a flush). None where the program has no such
histogram or flushed nothing."""

FLUSH = "logparser_batch_flush_seconds"


def read(run):
    flushes = run.delta(FLUSH + "_count", part="device")
    if flushes <= 0:
        return None
    return run.delta(FLUSH + "_sum") / flushes * 1e3
