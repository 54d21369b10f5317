"""transport_s_per_mline.bulk: client-side request seconds (send to
answer) outside the engine's phases — body decode, response encode,
sockets (serve/http.py) — per million lines."""


def read(run):
    wall = sum(r["done"] - r["sent"] for r in run.answered)
    return run.per_mline(wall - run.phase_s())
