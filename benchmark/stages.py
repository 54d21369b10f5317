"""What the per-layer readers of the program's own spans and counters
share: stage seconds (``logparser_stage_seconds``), phase CPU against
phase wall (``logparser_phase_cpu_seconds_total``,
``logparser_phase_seconds``) and the serving threads' CPU
(``logparser_request_cpu_seconds_total``), each as a Δ over the window.
The serving threads' CPU, not the process's: the harness serves from its
own process, so the process's CPU would hold the harness's work too (the
profiler's capture and export, the decode of the generator's report).

A program that does not record the span or counter reads None, so the
metric is left out of its line and nothing raises.
"""

from __future__ import annotations

STAGE = "logparser_stage_seconds"
PHASE_CPU = "logparser_phase_cpu_seconds_total"
PHASE_WALL = "logparser_phase_seconds_sum"
REQUEST_CPU = "logparser_request_cpu_seconds_total"


def _labels_of(run, name: str, label: str) -> set[str]:
    return {dict(ls).get(label) for n, ls in run.after if n == name}


def stage_s(run, *stages: str) -> float | None:
    """Δ seconds in ``stages``, or None where the program records none
    of them."""
    if not _labels_of(run, STAGE + "_count", "stage") & set(stages):
        return None
    return sum(run.delta(STAGE + "_sum", stage=s) for s in stages)


def cpu_share(run, *phases: str) -> float | None:
    """Δ thread CPU ÷ Δ wall over ``phases`` (over every phase that
    records CPU when none are named), or None without CPU counters or
    wall time."""
    recorded = _labels_of(run, PHASE_CPU, "phase") - {None}
    if not recorded:
        return None
    chosen = [p for p in (phases or sorted(recorded)) if p in recorded]
    wall = sum(run.delta(PHASE_WALL, phase=p) for p in chosen)
    if wall <= 0:
        return None
    return sum(run.delta(PHASE_CPU, phase=p) for p in chosen) / wall


def request_cpu_s(run) -> float | None:
    """Δ thread CPU seconds the server spent serving requests."""
    if not any(n == REQUEST_CPU for n, _ in run.after):
        return None
    return run.delta(REQUEST_CPU)


def per_request_ms(run, seconds: float | None) -> float | None:
    n = len(run.answered)
    return seconds / n * 1e3 if seconds is not None and n else None
