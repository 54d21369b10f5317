"""engine_ms.triage: engine milliseconds per request, the sum of all
its phases (runtime/engine.py PhaseTrace)."""


def read(run):
    n = len(run.answered)
    return run.phase_s() / n * 1e3 if n else None
