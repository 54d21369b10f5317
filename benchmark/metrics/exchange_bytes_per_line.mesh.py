"""exchange_bytes_per_line.mesh: Δ bytes the line-sharded step's halo
``ppermute``s and ``all_gather``s delivered between chips
(``logparser_shard_exchange_bytes_total``, computed by the program from
each launch's static shapes) per line answered. None where the program
has no such counter."""

COUNTER = "logparser_shard_exchange_bytes_total"


def read(run):
    if not run.lines or not any(n == COUNTER for n, _ in run.after):
        return None
    return run.delta(COUNTER) / run.lines
