"""What decides ``correct``: the served answers against the plain
reference, replayed in the order the engine finalized the requests.

After the window has closed and the server is gone, every request the
engine finalized (the warm-up and the window's) is made again from its
id and analyzed by the reference in a pool of worker processes that
never import JAX; the frequency penalty is then applied in finalize
order. Each served answer is compared with the reference's:

- ``mismatched_requests``: answers whose events differ in line, pattern,
  order or context, or whose line count differs, or that the finalize
  order does not place;
- ``max_score_delta``: the largest |served score − reference score|;
- ``unanswered``: requests sent (or due) that got no 200;
- ``fallbacks``: requests the golden fallback or the host route served.
"""

from __future__ import annotations

import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

from benchmark import reference as ref
from benchmark.traffic import WARMUP, Traffic, parse_request_id

# what a score that is not a number counts as
NOT_A_NUMBER = 1e300

_W: dict = {}


def _init(lib_dir, scoring, spec, config, seed, float32) -> None:
    lib = ref.Library(lib_dir)
    _W["lib"] = lib
    _W["scorer"] = ref.Scorer(lib, scoring, float32)
    _W["traffic"] = Traffic(spec, config, seed)


def _analyze(job) -> tuple:
    stream, k, n = job
    return ref.analyze(_W["lib"], _W["scorer"], _W["traffic"].logs(stream, k, n))


def request_sizes(traffic: Traffic, seconds: float):
    """``size(stream, k)`` of every request the load generator makes."""
    warm = traffic.warmup_sizes()
    planned = traffic.open_plan(seconds)[1] if traffic.open_loop else None

    def size(stream: int, k: int) -> int:
        if stream == WARMUP:
            return warm[k]
        return planned[k] if planned is not None else traffic.size(k)

    return size


def default_workers() -> int:
    return max(1, min(12, (os.cpu_count() or 2) - 1))


def replay(order: list[str], cell, seed: int, seconds: float, lib_dir: str,
           float32: bool = False, workers: int | None = None) -> dict:
    """rid → (line count, [(line, pattern id, score, context digest)])
    for the requests ``order`` lists, scored in that order."""
    traffic = Traffic(cell.traffic, cell.config, seed)
    size = request_sizes(traffic, seconds)
    jobs = []
    for rid in order:
        stream, k = parse_request_id(rid)
        jobs.append((stream, k, size(stream, k)))
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(
        max_workers=workers or default_workers(), mp_context=ctx,
        initializer=_init,
        initargs=(lib_dir, cell.config["scoring"], cell.traffic, cell.config,
                  seed, float32),
    ) as pool:
        parts = list(pool.map(_analyze, jobs))
    freq = ref.Frequency(cell.config["scoring"], float32)
    out = {}
    for rid, (n_lines, events) in zip(order, parts):
        out[rid] = (n_lines, [
            (line, pid, freq.score(pid, base), digest)
            for line, pid, base, digest in events
        ])
    return out


def compare(records: list[dict], expected: dict, attempted: int | None,
            fallbacks: float, limits: dict) -> dict:
    """{number: [value, limit]} for the served ``records``."""
    mismatched = 0
    worst = 0.0
    unanswered = 0
    window = 0
    for r in records:
        window += not r["warmup"]
        if r["status"] != 200:
            unanswered += 1
            continue
        want = expected.get(r["id"])
        if want is None or r["total_lines"] != want[0]:
            mismatched += 1
            continue
        got = r["events"]
        if [(e[0], e[1], e[3]) for e in got] != [(e[0], e[1], e[3]) for e in want[1]]:
            mismatched += 1
            continue
        for g, w in zip(got, want[1]):
            delta = abs(g[2] - w[2])
            worst = max(worst, delta if delta == delta else NOT_A_NUMBER)
    if attempted is not None:
        unanswered += max(0, attempted - window)
    return {
        "mismatched_requests": [mismatched, limits["mismatched_requests"]],
        "max_score_delta": [worst, limits["max_score_delta"]],
        "unanswered": [unanswered, limits["unanswered"]],
        "fallbacks": [fallbacks, limits["fallbacks"]],
    }


def is_correct(checks: dict) -> bool:
    return all(v <= lim for v, lim in checks.values())
