"""What a metric reader (``metrics/<name>.py``) is given: one run's
window records, its ``/metrics`` deltas and its trace reduction.

Every reader is ``read(run: Run) -> float | None``; None means the run
has nothing for that metric to read, and the metric is left out.
"""

from __future__ import annotations

import dataclasses
import math


def nearest_rank(values: list[float], p: float) -> float:
    """The ``p``-th percentile by nearest rank (``bench_common``'s
    arithmetic): the smallest value with at least ``p``% at or below."""
    xs = sorted(values)
    return xs[max(0, math.ceil(p / 100.0 * len(xs)) - 1)]


@dataclasses.dataclass
class Run:
    seconds: float
    setup_s: float
    # the window's requests, as the load generator recorded them (times
    # in seconds from the window's start; ``done`` None if no answer)
    window: list[dict]
    # /metrics before the window and after its last answer
    before: dict
    after: dict
    # trace_reduce.reduce() of the traced window, in a --trace 1 run
    trace: dict | None = None

    def delta(self, name: str, **labels) -> float:
        """Δ of the samples of ``name`` whose labels include ``labels``."""
        want = set(labels.items())

        def total(scrape):
            return sum(v for (n, ls), v in scrape.items()
                       if n == name and want <= set(ls))

        return total(self.after) - total(self.before)

    def phase_s(self, *phases: str) -> float:
        """Δ seconds the engine spent in ``phases`` (all when none)."""
        if not phases:
            return self.delta("logparser_phase_seconds_sum")
        return sum(self.delta("logparser_phase_seconds_sum", phase=p)
                   for p in phases)

    @property
    def answered(self) -> list[dict]:
        return [r for r in self.window if r["status"] == 200]

    @property
    def lines(self) -> int:
        """Lines of every window request answered: the requests the
        ``/metrics`` deltas cover."""
        return sum(r["lines"] for r in self.answered)

    def per_mline(self, seconds: float) -> float | None:
        return seconds / self.lines * 1e6 if self.lines else None

    def latencies_ms(self) -> list[float]:
        """From each request's due time to its answer; a request with no
        200 counts as answered a minute after the close."""
        out = []
        for r in self.window:
            done = r["done"] if r["status"] == 200 else self.seconds + 60.0
            out.append((done - r["due"]) * 1e3)
        return out
