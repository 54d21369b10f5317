"""host_cpu_s_per_mline.bulk: Δ thread CPU seconds of serving requests
(``logparser_request_cpu_seconds_total``: each ``/parse`` handler from its
start to after its write) per million lines answered."""

from benchmark.stages import request_cpu_s


def read(run):
    s = request_cpu_s(run)
    return run.per_mline(s) if s is not None else None
