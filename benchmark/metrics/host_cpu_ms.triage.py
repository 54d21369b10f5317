"""host_cpu_ms.triage: Δ thread CPU milliseconds of serving requests
(``logparser_request_cpu_seconds_total``: each ``/parse`` handler from its
start to after its write) per answered request."""

from benchmark.stages import per_request_ms, request_cpu_s


def read(run):
    return per_request_ms(run, request_cpu_s(run))
