"""transport_out_s_per_mline.bulk: Δ seconds in the /parse handler's
``transport.encode`` (``to_dict``, ``json.dumps``) and ``transport.write``
stages (serve/http.py) per million lines answered."""

from benchmark.stages import stage_s


def read(run):
    s = stage_s(run, "transport.encode", "transport.write")
    return run.per_mline(s) if s is not None else None
