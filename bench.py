"""Benchmark: end-to-end scored log-lines/sec on one chip.

Implements BASELINE.md config 2 (synthetic pod log, full built-in pattern
library, single device). The reference publishes no numbers (BASELINE.md);
``vs_baseline`` is therefore reported against the north-star target of
1M log-lines/sec/chip from BASELINE.json.

Backend contract: the golden host fallback is DISABLED for the bench,
and the bench measures the chip or nothing — with no TPU it emits a
``{"value": null}`` diagnostics line and exits 3
(bench_common.require_tpu), as does a backend that wedges mid-run; if no
campaign level completes, the bench raises. Consumers must check the
exit code, not just parse stdout.

Prints exactly one JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
     "platform": "tpu", "device": {...}, ...}
"""

from __future__ import annotations

import itertools
import os
import random
import sys

import bench_common  # noqa: F401  (sets LOG_PARSER_TPU_NO_FALLBACK=1 on import)
from log_parser_tpu.utils import corpus

N_LINES = int(sys.argv[sys.argv.index("--lines") + 1]) if "--lines" in sys.argv else 200_000
NORTH_STAR_LINES_PER_SEC = 1_000_000.0
# --repeat-ratio R: repeat-heavy corpus mode (bench_common.repeat_corpus)
# — ~R of each request's lines are zipf template draws, the rest unique.
# --line-cache-mb MB: serve through the exact-match line cache
# (runtime/linecache.py); 0/absent = cache off. Together they make
# cache-on vs cache-off a first-class BENCH_rNN comparison.
REPEAT_RATIO = (
    float(sys.argv[sys.argv.index("--repeat-ratio") + 1])
    if "--repeat-ratio" in sys.argv
    else None
)
LINE_CACHE_MB = (
    float(sys.argv[sys.argv.index("--line-cache-mb") + 1])
    if "--line-cache-mb" in sys.argv
    else 0.0
)
# --novel-ratio R: carve ~R of each repeat corpus into unseen
# generated-template lines (bench_common.NOVEL_TEMPLATES) — guaranteed
# cache misses shaped for the template miner. --miner: run the miner
# (review mode, so the bank never changes mid-measure) against that miss
# stream and embed its tap/cluster counters in the artifact; the
# BENCH_r12 companions are the same command with and without it.
NOVEL_RATIO = (
    float(sys.argv[sys.argv.index("--novel-ratio") + 1])
    if "--novel-ratio" in sys.argv
    else 0.0
)
MINER = "--miner" in sys.argv
# Distinct request payloads the repeat-mode stream cycles through. The
# line cache is a CROSS-request tier: with a single fixed payload every
# line (unique fillers included) becomes a hit after request #1 and the
# ratio stops meaning anything. Rotating a pool keeps template lines
# hitting while each payload's fillers miss on their first serving.
REPEAT_POOL_REQUESTS = 8
# --host-col: config-2 variant with one injected lookbehind pattern (a
# host-only column). Guards the VERDICT r3 #3 cliff: with the literal
# prefilter this must stay within ~2x of the clean number instead of
# collapsing to a full host-re scan per request.
HOST_COL = "--host-col" in sys.argv
# steady-state dwell per concurrency level of the serving campaign
CAMPAIGN_SECONDS = float(os.environ.get("LOG_PARSER_TPU_CAMPAIGN_S", "30"))


def build_corpus(n: int) -> str:
    return corpus.pod_log(n)


def main() -> None:
    metric = (
        "log_lines_scored_per_sec_per_chip_hostcol"
        if HOST_COL
        else "log_lines_scored_per_sec_per_chip"
    )
    if REPEAT_RATIO is not None:
        metric += f"_rr{int(round(REPEAT_RATIO * 100)):02d}"
    if LINE_CACHE_MB > 0:
        metric += "_lc"
    if NOVEL_RATIO > 0:
        metric += f"_nv{int(round(NOVEL_RATIO * 100)):02d}"
    if MINER:
        metric += "_miner"
    platform = bench_common.require_tpu(metric, "lines/s")

    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets
    from log_parser_tpu.runtime import AnalysisEngine

    sets = load_builtin_pattern_sets()
    if HOST_COL:
        from log_parser_tpu.models.pattern import (
            Pattern,
            PatternSet,
            PatternSetMetadata,
            PrimaryPattern,
        )

        sets = sets + [
            PatternSet(
                metadata=PatternSetMetadata(
                    library_id="hostcol", name="hostcol"
                ),
                patterns=[
                    Pattern(
                        id="hostcol-lb",
                        name="lookbehind host column",
                        severity="HIGH",
                        primary_pattern=PrimaryPattern(
                            regex=r"(?<=dial tcp )10\.0\.0\.\d+",
                            confidence=0.8,
                        ),
                    )
                ],
            )
        ]
    n_patterns = sum(len(s.patterns or []) for s in sets)
    # cold-start story (ROADMAP item 5): engine construction + first
    # analyze = bank build + the XLA compile set. With the persistent
    # compile cache warm the same wall-clock drops to a disk replay —
    # compare boot_seconds across a cold/warm artifact pair and read the
    # compile_cache hit/miss tally beside it.
    import time as _time

    _boot0 = _time.perf_counter()
    engine = AnalysisEngine(sets, ScoringConfig())
    assert not engine.fallback_to_golden, "bench must never serve from golden"
    if LINE_CACHE_MB > 0:
        engine.enable_line_cache(LINE_CACHE_MB)
    if MINER:
        assert LINE_CACHE_MB > 0, "--miner rides the line cache"
        # review mode: the worker drains/clusters (the cost under test)
        # but never swaps the bank mid-measure
        engine.enable_miner(mode="review")
    if REPEAT_RATIO is not None:
        rng = random.Random(0xC0FFEE)
        pool = [
            PodFailureData(
                pod={"metadata": {"name": "bench"}},
                logs=bench_common.repeat_corpus(
                    N_LINES, REPEAT_RATIO, f"r{t}", rng,
                    novel_ratio=NOVEL_RATIO,
                ),
            )
            for t in range(REPEAT_POOL_REQUESTS)
        ]
    else:
        pool = [
            PodFailureData(
                pod={"metadata": {"name": "bench"}}, logs=build_corpus(N_LINES)
            )
        ]
    _req = itertools.count()

    def next_data() -> PodFailureData:
        return pool[next(_req) % len(pool)]

    # first request pays the whole XLA compile set (or its disk replay):
    # stamp it as the boot cost before the warmup loop hides it
    _first = engine.analyze(next_data())
    assert _first.summary.significant_events > 0
    boot_seconds = _time.perf_counter() - _boot0

    # warmup + serial measure under the shared wedge wrapper and timing
    # rule (bench_common.measured_phase): a backend that wedges must
    # yield the diagnostics exit, not a hang
    bounded = bench_common.bounded_runner(metric, "lines/s", platform)
    result, _, best = bench_common.measured_phase(
        bounded, lambda: engine.analyze(next_data())
    )
    assert result.summary.significant_events > 0
    serial_rate = N_LINES / best

    campaign_s = CAMPAIGN_SECONDS

    # Chip throughput under serving load: ``analyze_pipelined`` overlaps
    # request N+1's ingest + device execution with request N's host-side
    # sync/finalize (only the frequency-coupled finish serializes), so
    # concurrent streams measure what the chip actually sustains — the
    # serial loop leaves it idle during every host round-trip. The
    # campaign holds each concurrency level at steady state for
    # >= CAMPAIGN_SECONDS of wall clock (VERDICT r3 weak #5: the old
    # 4x2-request burst under a best-of selector was too thin a basis
    # for the headline); the serial rate stays in the artifact for
    # comparability.
    def analyze_once() -> None:
        r = engine.analyze_pipelined(next_data())
        assert r.summary.significant_events > 0

    curve, campaign_error = bench_common.run_campaign(
        analyze_once, N_LINES, campaign_s, request_floor_s=best
    )
    measured = [p for p in curve if "error" not in p]
    if not measured:  # nothing steady-state survived — a number here would be a lie
        raise RuntimeError(f"campaign produced no complete level: {campaign_error}")
    # headline methodology is PINNED to the sustained serving throughput
    # at the curve's best point, with that point named in the artifact
    # (not max(serial, pipelined) — that would silently flip methodology
    # between runs); the serial single-stream rate rides alongside
    headline = max(measured, key=lambda p: p["lines_per_sec"])
    extra = {}
    if campaign_error is not None:
        extra["campaign_error"] = campaign_error
    if REPEAT_RATIO is not None:
        extra["repeat_ratio"] = REPEAT_RATIO
        extra["pool_requests"] = len(pool)
    if engine.line_cache is not None:
        extra["line_cache_mb"] = LINE_CACHE_MB
        extra["line_cache"] = engine.line_cache.stats()
    if NOVEL_RATIO > 0:
        extra["novel_ratio"] = NOVEL_RATIO
    if engine.miner is not None:
        extra["miner"] = engine.miner.stats()
        engine.miner.stop()
    from log_parser_tpu.utils import xlacache

    extra["boot_seconds"] = round(boot_seconds, 3)
    extra["compile_cache"] = xlacache.stats()
    obs = getattr(engine, "obs", None)
    if obs is not None:
        # the same Prometheus exposition GET /metrics serves, snapshotted
        # at campaign end — the artifact carries the full counter state
        # the run produced, not just the headline
        extra["metrics"] = obs.registry.render()
    bench_common.emit(
        metric,
        headline["lines_per_sec"],
        "lines/s",
        round(headline["lines_per_sec"] / NORTH_STAR_LINES_PER_SEC, 4),
        platform,
        n_lines=N_LINES,
        n_patterns=n_patterns,
        serial_lines_per_sec=round(serial_rate, 1),
        pipeline_concurrency=headline["concurrency"],
        throughput_curve=curve,
        campaign_seconds=campaign_s,
        # the headline key predates the pipelined methodology; this field
        # disambiguates artifacts across versions (r1-r2: serial best-of,
        # r3: 4x2-burst best-of-2, r4+: steady-state curve, headline at
        # the named best concurrency)
        methodology="pipelined-sustained-v3",
        **extra,
    )


if __name__ == "__main__":
    main()
