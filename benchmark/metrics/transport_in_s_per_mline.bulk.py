"""transport_in_s_per_mline.bulk: Δ seconds in the /parse handler's
``transport.read`` (socket read of the body) and ``transport.decode``
(``json.loads``, ``PodFailureData.from_dict``, the line count) stages
(serve/http.py) per million lines answered."""

from benchmark.stages import stage_s


def read(run):
    s = stage_s(run, "transport.read", "transport.decode")
    return run.per_mline(s) if s is not None else None
