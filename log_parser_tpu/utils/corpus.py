"""Synthetic pod logs in the benchmark's shape (BASELINE config 2).

Six failure lines sit at fixed residues of a 997-line period among unique
INFO ticks. ``bench.build_corpus`` and ``chip_smoke.py`` both draw from
here; importing this module has no side effect.
"""

from __future__ import annotations

PERIOD = 997
SPECIALS = {
    5: "java.lang.OutOfMemoryError: Java heap space",
    3: "[Full GC (Ergonomics) 255M->250M(256M), 0.41 secs]",
    250: "dial tcp 10.0.0.7:5432: Connection refused",
    500: "Warning: Liveness probe failed: HTTP 503",
    700: "    at com.example.Service.handle(Service.java:42)",
    701: "ERROR request failed with IllegalStateException",
}


def pod_log(n: int, rng=None) -> str:
    """``n`` lines. Without ``rng`` line ``i`` is of kind ``i % PERIOD``
    and stamped ``07:{i%60}:{i%60}``; with a numpy ``Generator`` the kinds,
    minutes and seconds are drawn from it."""
    if rng is None:
        kinds = [i % PERIOD for i in range(n)]
        minutes = seconds = [i % 60 for i in range(n)]
    else:
        kinds = rng.integers(0, PERIOD, n).tolist()
        minutes = rng.integers(0, 60, n).tolist()
        seconds = rng.integers(0, 60, n).tolist()
    return "\n".join(
        SPECIALS.get(k)
        or f"2026-07-29T07:{m:02d}:{s:02d}Z INFO reconcile tick {i} status=ok"
        for i, (k, m, s) in enumerate(zip(kinds, minutes, seconds))
    )
