"""residual_fill_share.tail: Δ unique miss lines of the micro-batcher's
line-cache residual dispatches (``logparser_batch_residual_rows_total``)
÷ Δ padded rows they ran (``logparser_batch_residual_slots_total``,
runtime/batcher.py). None where the program has no such counters or ran
no residual."""

ROWS = "logparser_batch_residual_rows_total"
SLOTS = "logparser_batch_residual_slots_total"


def read(run):
    slots = run.delta(SLOTS)
    return run.delta(ROWS) / slots if slots > 0 else None
