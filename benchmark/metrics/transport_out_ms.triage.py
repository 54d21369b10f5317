"""transport_out_ms.triage: Δ milliseconds in the /parse handler's
``transport.encode`` and ``transport.write`` stages (serve/http.py) per
answered request."""

from benchmark.stages import per_request_ms, stage_s


def read(run):
    return per_request_ms(run, stage_s(run, "transport.encode", "transport.write"))
