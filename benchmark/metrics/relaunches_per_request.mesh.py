"""relaunches_per_request.mesh: Δ SPMD launches beyond each request's
first (``logparser_shard_relaunches_total``: a shard's matches overflowed
its record bucket, so parallel/sharded.py's K ladder ran the whole step
again) per answered request. None where the program has no such counter."""

COUNTER = "logparser_shard_relaunches_total"


def read(run):
    if not run.answered or not any(n == COUNTER for n, _ in run.after):
        return None
    return run.delta(COUNTER) / len(run.answered)
