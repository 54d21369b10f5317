"""record_fill_share.mesh: Δ live match records
(``logparser_shard_records_total``) ÷ Δ per-shard record slots the
line-sharded step read back (``logparser_shard_record_slots_total``,
shards × the K bucket that held them). None where the program has no
such counters or read back no slot."""

RECORDS = "logparser_shard_records_total"
SLOTS = "logparser_shard_record_slots_total"


def read(run):
    if not any(n == SLOTS for n, _ in run.after):
        return None
    slots = run.delta(SLOTS)
    return run.delta(RECORDS) / slots if slots > 0 else None
