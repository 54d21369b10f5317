"""Shared harness for the bench scripts (bench.py, bench_bank.py,
bench_latency.py).

A bench measures the chip or nothing. :func:`require_tpu` checks the
device in-process, before any engine exists: when JAX finds no TPU the
bench prints a ``{"value": null}`` diagnostics line and exits 3
(:func:`exit_null`) — there is no CPU floor, and no child process ever
touches JAX (a chip belongs to one process; a parent that has touched
JAX holds it). Consumers must check the exit code.

Round-1 postmortem (VERDICT.md r1): the engine's golden host fallback
turned a benchmark into a silent multi-minute pure-Python crawl, so
importing this module sets ``LOG_PARSER_TPU_NO_FALLBACK=1``; import it
before constructing any engine. A backend that wedges mid-run is still
bounded (:func:`run_bounded`): the bench emits the null line instead of
hanging.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

os.environ["LOG_PARSER_TPU_NO_FALLBACK"] = "1"

# Wall budget for device init and the first (compiling) call of a bench
# phase; steady-state phases derive theirs from the observed warmup.
INIT_BUDGET_S = 600.0

#: The device this process measured on, as JAX reports it — filled by
#: require_tpu() and stamped into every artifact by emit().
last_device: dict | None = None


def timeit(fn, n: int = 3, warmup: int = 1) -> float:
    """Best-of-n wall time after warmup — THE timing rule shared by every
    probe script (tools/probe_*.py), so methodology changes land in one
    place."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts)


# ------------------------------------------------------- repeat-heavy mode
# Shared by bench.py and bench_latency.py (``--repeat-ratio``): a
# synthetic repeat-heavy stream for exercising the exact-match line cache
# (runtime/linecache.py). Template lines are drawn zipf (weight 1/rank)
# from a small pool — the shape of real fleet logs, where a handful of
# templates dominate — and the remaining lines carry a unique tag so they
# can never hit the cache.

# Benign templates dominate the head ranks and the matching templates sit
# at the tail — real fleet logs are overwhelmingly routine (the zipf head
# is heartbeats and reconcile ticks), and a pool where every template
# produced an event would let result-assembly cost (identical cache-on
# and cache-off) drown the cube savings the mode exists to measure.
REPEAT_TEMPLATES = (
    "2026-07-29T07:00:00Z INFO reconcile tick status=ok",
    "INFO steady-state heartbeat marker",
    'GET /healthz 200 17b "kube-probe/1.29"',
    "INFO syncing deployment default/web replicas=3",
    "INFO volume mount ok pvc-data-0",
    "INFO leader-election renewed lease",
    "INFO configmap checksum unchanged",
    "INFO endpoint slice updated 10.0.3.17:8080",
    "INFO image already present on machine",
    "INFO scheduled pod web-7f9c onto node-4",
    "INFO readiness gate passed",
    "INFO garbage collector scanned 312 objects",
    "INFO certificate rotation not due",
    "ERROR request failed with IllegalStateException",
    "dial tcp 10.0.0.7:5432: Connection refused",
    "java.lang.OutOfMemoryError: Java heap space",
)

_ZIPF_CUM: list[float] = []
for _rank in range(len(REPEAT_TEMPLATES)):
    _ZIPF_CUM.append((_ZIPF_CUM[-1] if _ZIPF_CUM else 0.0) + 1.0 / (_rank + 1))


def zipf_template(u: float) -> str:
    """Map uniform ``u`` in [0, 1) to a template with P(rank) ∝ 1/(rank+1)."""
    x = u * _ZIPF_CUM[-1]
    for rank, cum in enumerate(_ZIPF_CUM):
        if x < cum:
            return REPEAT_TEMPLATES[rank]
    return REPEAT_TEMPLATES[-1]


def hash01(x: int) -> float:
    """Deterministic uniform [0, 1) from an integer — lets a corpus
    builder stay a pure function of its indices (the latency sweep's
    prewarm regenerates content by index and must see identical lines)."""
    x = (x * 2654435761) & 0xFFFFFFFF
    x ^= x >> 16
    x = (x * 2246822519) & 0xFFFFFFFF
    x ^= x >> 13
    return x / 4294967296.0


# ``--novel-ratio`` (bench.py): unseen generated-template lines for the
# template miner (log_parser_tpu/mining/). Each is a fixed token skeleton
# with numeric wildcard slots — exactly the shape the clusterer groups —
# and none appears in REPEAT_TEMPLATES or matches a builtin pattern, so
# every draw is a guaranteed line-cache miss feeding the miner tap.
NOVEL_TEMPLATES = (
    "replication backlog drained on shard {a} after {b} entries",
    "checkpoint upload finished for epoch {a} in {b} ms",
    "frobnicator subsystem rebalanced queue {a} depth {b}",
    "thermal governor stepped clock domain {a} to {b} mhz",
)


def novel_line(u: float, i: int) -> str:
    """Map uniform ``u`` and a line index to a generated-template line:
    the skeleton repeats, the slot values never do."""
    tmpl = NOVEL_TEMPLATES[int(u * len(NOVEL_TEMPLATES)) % len(NOVEL_TEMPLATES)]
    return tmpl.format(a=i % 8191, b=(i * 37) % 9973)


def repeat_corpus(
    n: int, ratio: float, tag: str, rng, novel_ratio: float = 0.0
) -> str:
    """``n`` lines, ~``ratio`` of them zipf template draws, the rest
    unique filler stamped with ``tag``. Every ~997th filler still carries
    a matching ERROR so the stream produces events at any ratio.

    ``novel_ratio`` carves that fraction of lines into unseen
    generated-template draws (:data:`NOVEL_TEMPLATES`) for miner benches;
    the default 0.0 takes no extra RNG draws, so miner-off corpora are
    bit-identical to pre-knob ones."""
    rows = []
    for i in range(n):
        if novel_ratio and rng.random() < novel_ratio:
            rows.append(novel_line(rng.random(), i))
        elif rng.random() < ratio:
            rows.append(zipf_template(rng.random()))
        elif i % 997 == 701:
            rows.append(
                f"ERROR request failed with IllegalStateException uniq={tag}.{i}"
            )
        else:
            rows.append(f"INFO unique filler {tag}.{i} status=ok")
    return "\n".join(rows)


# Bounded-drain floor for a campaign level (seconds): in-flight requests
# normally finish within ~p99 after the dwell, but a WEDGED backend never
# returns — an unbounded join would hang the bench with no artifact at
# all. 240 s sits well above any slow-but-live request so "wedged" in an
# artifact means wedged, not slow. Module-level so tests can shrink it.
DRAIN_FLOOR_S = 240.0

# Level order: a strong candidate (C=2) runs FIRST so a good number is
# banked before any heavier multi-stream stress. The payoff is the
# degrade path: a level that fails degrades the artifact to the
# already-banked levels, and with C=2 first the banked set is worth
# keeping.
CAMPAIGN_LEVELS = (2, 1, 4, 8)


def wedge_failure(prefix: str, errors: list) -> str:
    """One shared format for a wedged fan-out's failure text: a sibling
    worker's error is the likely root cause, so it rides along (repr
    truncated to 300 chars — backend errors carry multi-KB tracebacks
    and artifacts are one JSON line)."""
    if errors:
        prefix += f"; first worker error: {repr(errors[0])[:300]}"
    return prefix


def join_bounded(threads, budget_s: float) -> bool:
    """Join daemon ``threads`` under one shared wall budget; True iff any
    is still alive afterwards (a wedged backend — callers degrade or
    exit_null instead of hanging).  THE wedge-detection rule shared by
    every bench fan-out, so drain-policy changes land in one place.
    Threads must be daemons: a wedged one is abandoned, not waited out.
    """
    deadline = time.monotonic() + budget_s
    for th in threads:
        th.join(max(0.0, deadline - time.monotonic()))
    return any(th.is_alive() for th in threads)


def run_bounded(workers: list, budget_s: float, metric: str, unit: str,
                platform: str, what: str) -> list:
    """Run ``workers`` (zero-arg callables) in daemon threads under one
    bounded join; returns their results in order.  A wedge (any worker
    still alive after the budget) emits the null diagnostics artifact —
    with the first sibling error as the likely root cause — and exits 3;
    a worker error (all workers finished) re-raises.  The ONE wrapper
    every bench fan-out goes through, so the wedge policy (message
    format, exit_null-on-wedge, error propagation) cannot drift between
    benches."""
    results: list = [None] * len(workers)
    errors: list[BaseException] = []

    def wrap(i: int, fn):
        def inner() -> None:
            try:
                results[i] = fn()
            except BaseException as exc:  # noqa: BLE001 - re-raised below
                errors.append(exc)

        return inner

    threads = [
        threading.Thread(target=wrap(i, fn), daemon=True)
        for i, fn in enumerate(workers)
    ]
    for th in threads:
        th.start()
    if join_bounded(threads, budget_s):
        exit_null(
            metric, unit, platform,
            wedge_failure(
                f"wedged: no progress after {budget_s:.0f}s ({what})", errors
            ),
        )
    if errors:
        raise errors[0]
    return results


def run_bounded_one(fn, budget_s: float, metric: str, unit: str,
                    platform: str, what: str):
    """Single-worker :func:`run_bounded` — the common shape for serial
    bench phases (device init, warmup, the timed measure)."""
    return run_bounded([fn], budget_s, metric, unit, platform, what)[0]


def bounded_runner(metric: str, unit: str, platform):
    """Bind a bench's artifact identity once and get its per-phase wedge
    wrapper ``bounded(fn, budget_s, what)`` — so every serial bench
    carries the identical wrapper instead of a local re-binding copy.

    ``platform`` may be the label string or a zero-arg getter: a bench
    that refines its label mid-run (bench_mesh's real mode reports the
    actual device platform discovered during init) passes
    ``lambda: platform`` so every phase reads the CURRENT label — a
    frozen stale label on a wedge artifact would be a mislabel."""

    def bounded(fn, budget_s: float, what: str):
        p = platform() if callable(platform) else platform
        return run_bounded_one(fn, budget_s, metric, unit, p, what)

    return bounded


#: Run count of every timed measure phase (bench_common.timeit n=...);
#: one constant so measure_budget and the timeit call sites cannot drift.
MEASURE_RUNS = 3


def measure_budget(warmup_dt: float, n: int = MEASURE_RUNS) -> float:
    """Wedge budget for an n-run timed measure phase, derived from the
    OBSERVED warmup duration: warmup includes compilation, so 5x it
    over-covers a steady-state run — a slower host or a bigger workload
    scales the budget instead of tripping a false wedge.  One formula so
    benches cannot drift."""
    return n * max(60.0, 5.0 * warmup_dt)


def measured_phase(bounded, fn, n: int = MEASURE_RUNS):
    """THE serial measurement sequence shared by every bench: one warmup
    call of ``fn`` under the cold-start budget (compiles + caches), then
    best-of-``n`` timing under the warmup-derived wedge budget.  Returns
    ``(warmup_result, warmup_dt, best_seconds)``.  ``bounded`` is the
    bench's :func:`bounded_runner` wrapper."""
    w0 = time.perf_counter()
    result = bounded(fn, INIT_BUDGET_S, "warmup")
    warmup_dt = time.perf_counter() - w0
    best = bounded(
        lambda: timeit(fn, n=n, warmup=0),
        measure_budget(warmup_dt, n),
        "measure",
    )
    return result, warmup_dt, best


def run_campaign(
    analyze_once,
    n_lines: int,
    campaign_s: float,
    levels: tuple[int, ...] = CAMPAIGN_LEVELS,
    request_floor_s: float = 0.0,
) -> tuple[list[dict], str | None]:
    """Hold each concurrency level at steady state for ``campaign_s`` of
    wall clock, calling ``analyze_once`` from ``concurrency`` client
    threads (VERDICT r3 weak #5: a burst under a best-of selector is too
    thin a basis for a headline). Engine-agnostic via the callback — THE
    steady-state measurement methodology, shared like :func:`timeit`.

    Returns ``(curve, campaign_error)``: the curve sorted by concurrency,
    one dict per level — measured levels carry requests/wall_s/
    lines_per_sec/percentiles, a failed level carries ``"error"`` and
    ends the campaign (a dead backend fails every later level anyway,
    slowly). ``campaign_error`` is None iff every level completed. A
    level whose in-flight requests never return (wedged backend) is
    detected by a bounded drain and recorded like an error — the old
    raise-on-first-error destroyed the whole artifact instead.
    """
    curve_points: dict[int, dict] = {}
    campaign_error = None
    for concurrency in levels:
        stop = threading.Event()
        errors: list[BaseException] = []
        lat: list[float] = []
        lock = threading.Lock()

        def client() -> None:
            try:
                while not stop.is_set():
                    r0 = time.perf_counter()
                    analyze_once()
                    rd = time.perf_counter() - r0
                    with lock:
                        lat.append(rd)
            except BaseException as exc:
                errors.append(exc)
                stop.set()

        # daemon threads: a request wedged inside a dying backend must
        # not block process exit after the bounded drain below gives up
        threads = [
            threading.Thread(target=client, daemon=True)
            for _ in range(concurrency)
        ]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        stop.wait(campaign_s)  # a failing client ends the dwell early
        stop.set()
        # the drain must scale with REQUEST size, not just the dwell: a
        # 1M-line request is ~5x a 200k one and a C=8 queue multiplies
        # further. ``request_floor_s`` is the caller's measured serial
        # request time (x10 covers a full C=8 queue depth); the max
        # latency observed IN this level adapts to live conditions the
        # caller couldn't have measured
        with lock:
            observed = max(lat, default=0.0)
        drain_s = max(
            DRAIN_FLOOR_S,
            4.0 * campaign_s,
            10.0 * request_floor_s,
            5.0 * observed,
        )
        wedged = join_bounded(threads, drain_s)
        dt = time.perf_counter() - t0
        failure = None
        if wedged:
            failure = wedge_failure(
                f"wedged: requests still in flight after {drain_s:.0f}s drain",
                errors,
            )
        elif errors:
            # 300-char truncation: backend errors carry multi-KB
            # tracebacks and the artifact is one JSON line
            failure = repr(errors[0])[:300]
        if failure is not None:
            campaign_error = f"concurrency {concurrency}: {failure}"
            curve_points[concurrency] = {"concurrency": concurrency, "error": failure}
            break
        lat.sort()
        n = len(lat)
        curve_points[concurrency] = {
            "concurrency": concurrency,
            "requests": n,
            "wall_s": round(dt, 2),
            "lines_per_sec": round(n * n_lines / dt, 1),
            # nearest-rank percentiles: rank ceil(q*n), 1-based
            "p50_ms": round(1e3 * lat[max(0, -(-50 * n // 100) - 1)], 1)
            if n
            else None,
            "p99_ms": round(1e3 * lat[max(0, -(-99 * n // 100) - 1)], 1)
            if n
            else None,
        }
    return [curve_points[c] for c in sorted(curve_points)], campaign_error


def require_tpu(metric: str, unit: str) -> str:
    """The in-process device check every bench starts with: returns
    ``"tpu"`` when JAX's first device is a TPU, otherwise emits the null
    diagnostics artifact and exits 3. JAX_PLATFORMS alone decides what
    JAX finds."""
    global last_device
    import jax

    devices = jax.devices()
    d = devices[0]
    last_device = {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(devices),
    }
    if d.platform != "tpu":
        exit_null(
            metric, unit, d.platform,
            f"no TPU: JAX found {len(devices)} {d.platform!r} device(s); "
            "a bench measures the chip or nothing",
        )
    return "tpu"


def host_load() -> dict | None:
    """The host's concurrent-load fingerprint at measurement time: a
    number means nothing without knowing what else the box was doing.
    Stamped into every artifact; tools/bench_diff.py marks comparisons
    whose sides ran under very different load advisory-only."""
    try:
        one, five, fifteen = os.getloadavg()
    except OSError:  # pragma: no cover - platform without getloadavg
        return None
    return {
        "loadavg": [round(one, 3), round(five, 3), round(fifteen, 3)],
        "cpus": os.cpu_count(),
    }


def exit_null(metric: str, unit: str, platform: str, error: str) -> None:
    """Emit the null-value diagnostics artifact and hard-exit: used when
    no honest number can be produced (no TPU, wedged backend)."""
    print(
        json.dumps(
            {
                "metric": metric,
                "value": None,
                "unit": unit,
                "vs_baseline": None,
                "platform": platform,
                "error": error,
                "device": last_device,
                "host_load": host_load(),
            }
        )
    )
    sys.exit(3)


def emit(metric: str, value: float, unit: str, vs_baseline: float | None,
         platform: str, **extra) -> None:
    """Print the single artifact JSON line, embedding the platform label
    and the device as JAX reported it."""
    doc = {
        "metric": metric,
        "value": value,
        "unit": unit,
        "vs_baseline": vs_baseline,
        "platform": platform,
    }
    doc.update(extra)
    load = host_load()
    if load is not None:
        doc["host_load"] = load
    if last_device is not None:
        doc["device"] = last_device
    print(json.dumps(doc))
