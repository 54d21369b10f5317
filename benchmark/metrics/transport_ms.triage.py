"""transport_ms.triage: mean client latency (send to answer) less the
engine's milliseconds per request — transport and admission
(serve/http.py, serve/admission.py)."""


def read(run):
    n = len(run.answered)
    if not n:
        return None
    client_ms = sum(r["done"] - r["sent"] for r in run.answered) / n * 1e3
    return client_ms - run.phase_s() / n * 1e3
