"""requests_per_flush.tail: Δ requests that rode a micro-batch
(``logparser_requests_batched_total``) ÷ Δ flushes
(``logparser_batches_flushed_total``, runtime/batcher.py): how many
requests each flush coalesced. None where nothing flushed."""

REQUESTS = "logparser_requests_batched_total"
FLUSHES = "logparser_batches_flushed_total"


def read(run):
    flushes = run.delta(FLUSHES)
    return run.delta(REQUESTS) / flushes if flushes > 0 else None
