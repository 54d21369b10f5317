"""finish_cpu_share.bulk: Δ thread CPU ÷ Δ wall of the ``extract``,
``finalize`` and ``assemble`` phases (``logparser_phase_cpu_seconds_total``
against ``logparser_phase_seconds``): near 1 the finish work runs on a
core, far below it waits (the GIL, the device)."""

from benchmark.stages import cpu_share


def read(run):
    return cpu_share(run, "extract", "finalize", "assemble")
