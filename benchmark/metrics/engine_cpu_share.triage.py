"""engine_cpu_share.triage: Δ thread CPU ÷ Δ wall over every engine phase
that records CPU (``logparser_phase_cpu_seconds_total`` against
``logparser_phase_seconds``)."""

from benchmark.stages import cpu_share


def read(run):
    return cpu_share(run)
