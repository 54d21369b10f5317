"""Streaming benchmark: continuous ``/parse`` micro-batches, p50/p99 latency.

Implements BASELINE.md config 5. The reference publishes no latency numbers
(BASELINE.md — `README.md` and docs contain none), so the target is
"establish". Default drives the engine directly; ``--http`` exercises the
full REST stack on a local server for end-to-end request latency.

Prints exactly one JSON line:
    {"metric": ..., "value": p99_ms, "unit": "ms", "vs_baseline": p50_ms}

``--sweep`` switches to the micro-batching concurrency sweep (ISSUE 3
acceptance): 1/4/16/64 concurrent clients x batching off/on against the
engine directly, per-level p50/p99 plus aggregate lines/sec. The
headline value is the 16-client batching-ON throughput, vs_baseline the
16-client OFF throughput, with the full curve in ``sweep``. Defaults to
small 64-line corpora (where per-request dispatch overhead dominates
and coalescing pays); ``--lines`` overrides.

``--stream`` switches to the follow-mode time-to-first-detection
scenario (ISSUE 9 acceptance): each corpus is replayed as a streaming
session in ``--chunk-lines``-line chunks at a fixed ``--chunk-cadence-ms``
arrival pace (default 5 ms; 0 = back-to-back compute-only), and TTFD is
the wall time from replay start to the first ``emit`` frame — measured
against blob-mode end-to-end latency on same-shaped corpora, where
end-to-end charges blob mode the full replay window (collect-then-POST
cannot fire until the tail has finished arriving) plus one-shot
``analyze()``. The headline value is p50 TTFD, vs_baseline the blob-mode
p50; the full percentiles, the TTFD/blob ratio, and the session counter
block ride in the artifact. Combine with
``--repeat-ratio``/``--line-cache-mb`` for the repeat-heavy tail-follow
shape the streaming layer is built for.
"""

from __future__ import annotations

import json
import sys
import threading
import time

import bench_common  # noqa: F401  (sets LOG_PARSER_TPU_NO_FALLBACK=1 on import)

SWEEP = "--sweep" in sys.argv
BATCH_LINES = (
    int(sys.argv[sys.argv.index("--lines") + 1])
    if "--lines" in sys.argv
    else (16 if SWEEP else 512)
)
REQUESTS = int(sys.argv[sys.argv.index("--requests") + 1]) if "--requests" in sys.argv else 60
USE_HTTP = "--http" in sys.argv
SWEEP_LEVELS = (1, 4, 16, 64)
SWEEP_WAIT_MS = (
    float(sys.argv[sys.argv.index("--batch-wait-ms") + 1])
    if "--batch-wait-ms" in sys.argv
    else 12.0
)
SWEEP_BATCH_MAX = (
    int(sys.argv[sys.argv.index("--batch-max") + 1])
    if "--batch-max" in sys.argv
    else 16
)
# N concurrent clients: measures how well the pipelined serving path
# (engine.analyze_pipelined) overlaps ingest/device work across requests;
# 1 = the sequential stream
CONCURRENCY = (
    int(sys.argv[sys.argv.index("--concurrency") + 1])
    if "--concurrency" in sys.argv
    else 1
)
# --repeat-ratio R: ~R of each micro-batch's lines become zipf template
# draws (bench_common.REPEAT_TEMPLATES), the rest stay unique per (i, j).
# --line-cache-mb MB: serve through the exact-match line cache
# (runtime/linecache.py); 0/absent = cache off.
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
# --stream: follow-mode TTFD scenario (runtime/stream.py sessions)
STREAM = "--stream" in sys.argv
CHUNK_LINES = (
    int(sys.argv[sys.argv.index("--chunk-lines") + 1])
    if "--chunk-lines" in sys.argv
    else 16
)
CHUNK_CADENCE_MS = (
    float(sys.argv[sys.argv.index("--chunk-cadence-ms") + 1])
    if "--chunk-cadence-ms" in sys.argv
    else 5.0
)


def micro_batch(i: int, n: int) -> str:
    if REPEAT_RATIO is not None:
        # pure function of (i, j) via hash01 so the sweep prewarm, which
        # regenerates content by index, sees identical lines and shapes
        rows = []
        for j in range(n):
            u = i * 131 + j
            if bench_common.hash01(u) < REPEAT_RATIO:
                rows.append(
                    bench_common.zipf_template(
                        bench_common.hash01(u ^ 0x9E3779B9)
                    )
                )
            else:
                rows.append(f"INFO tick {i}.{j} status=ok")
        return "\n".join(rows)
    rows = []
    for j in range(n):
        m = (i * 131 + j) % 97
        if m == 11:
            rows.append("java.lang.OutOfMemoryError: Java heap space")
        elif m == 13:
            rows.append("dial tcp 10.0.0.7:5432: Connection refused")
        elif m == 17:
            rows.append("ERROR request failed with IllegalStateException")
        else:
            rows.append(f"INFO tick {i}.{j} status=ok")
    return "\n".join(rows)


def metric_suffix() -> str:
    s = ""
    if REPEAT_RATIO is not None:
        s += f"_rr{int(round(REPEAT_RATIO * 100)):02d}"
    if LINE_CACHE_MB > 0:
        s += "_lc"
    return s


def percentile(sorted_vals: list[float], q: float) -> float:
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def sweep_main() -> None:
    metric = f"parse_agg_lines_per_s_c16_batched_{BATCH_LINES}line" + metric_suffix()
    platform = bench_common.require_tpu(metric, "lines/s")

    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets
    from log_parser_tpu.runtime import AnalysisEngine

    engine = AnalysisEngine(load_builtin_pattern_sets(), ScoringConfig())
    if LINE_CACHE_MB > 0:
        engine.enable_line_cache(LINE_CACHE_MB)

    def run_level(batching: bool, c: int, per_client: int) -> dict:
        per_thread: list[list[float]] = [[] for _ in range(c)]

        def client(ci: int):
            def inner() -> None:
                for j in range(per_client):
                    data = PodFailureData(
                        pod={"metadata": {"name": "sweep"}},
                        logs=micro_batch(ci * per_client + j, BATCH_LINES),
                    )
                    t0 = time.perf_counter()
                    if batching:
                        engine.analyze_batched(data)
                    else:
                        engine.analyze_pipelined(data)
                    per_thread[ci].append((time.perf_counter() - t0) * 1e3)

            return inner

        n_requests = c * per_client
        budget_s = max(bench_common.DRAIN_FLOOR_S, 10.0 * n_requests)
        mode = "on" if batching else "off"
        t0 = time.perf_counter()
        bench_common.run_bounded(
            [client(ci) for ci in range(c)],
            budget_s,
            metric,
            "lines/s",
            platform,
            f"sweep c{c} batching={mode}",
        )
        wall = time.perf_counter() - t0
        lat = sorted(x for vals in per_thread for x in vals)
        return {
            "concurrency": c,
            "batching": mode,
            "requests": n_requests,
            "wall_s": round(wall, 3),
            "lines_per_sec": round(n_requests * BATCH_LINES / wall, 1),
            "p50_ms": round(percentile(lat, 0.50), 3),
            "p99_ms": round(percentile(lat, 0.99), 3),
        }

    def prewarm_batcher(batcher) -> None:
        """Compile every (R, B, T) shape the sweep can realize BEFORE the
        timed levels: group the request stream's corpora by encoded shape,
        then coalesce exact power-of-two batches of each group through the
        real batcher path. Without this, stray XLA compiles of the vmapped
        program land inside a timed window and read as 4-second p99s."""
        from log_parser_tpu.native.ingest import Corpus

        by_shape: dict[tuple, list[int]] = {}
        for i in range(97):  # the micro_batch content cycle
            corpus = Corpus(
                micro_batch(i, BATCH_LINES),
                min_rows=engine._corpus_min_rows(),
            )
            by_shape.setdefault(corpus.encoded.u8.shape, []).append(i)
        old_wait = batcher.wait_s
        batcher.wait_s = 0.25  # hold each round open until fully enqueued
        try:
            for idxs in by_shape.values():
                r = 1
                while r <= batcher.batch_max:
                    pend = [
                        batcher._enqueue(
                            PodFailureData(
                                pod={"metadata": {"name": "warm"}},
                                logs=micro_batch(i, BATCH_LINES),
                            ),
                            None,
                        )
                        for i in (idxs * r)[:r]
                    ]
                    for p in pend:
                        p.done.wait()
                    r <<= 1
        finally:
            batcher.wait_s = old_wait

    curve = []
    batcher_stats = None
    for batching in (False, True):
        if batching:
            batcher = engine.enable_batching(
                wait_ms=SWEEP_WAIT_MS, batch_max=SWEEP_BATCH_MAX
            )
            bounded = bench_common.bounded_runner(metric, "lines/s", platform)
            bounded(
                lambda: prewarm_batcher(batcher),
                bench_common.INIT_BUDGET_S,
                "batch prewarm",
            )
        for c in SWEEP_LEVELS:
            # warmup round (untimed): the unbatched R=1 shapes, and with
            # batching on the residual scheduler timing at this fan-in
            run_level(batching, c, 2)
            curve.append(run_level(batching, c, max(3, REQUESTS // c)))
        if batching:
            batcher_stats = engine.batcher.stats()
            engine.batcher.close()
            engine.batcher = None

    def level(mode: str, c: int) -> dict:
        return next(
            r for r in curve if r["batching"] == mode and r["concurrency"] == c
        )

    extra = {}
    if REPEAT_RATIO is not None:
        extra["repeat_ratio"] = REPEAT_RATIO
    if engine.line_cache is not None:
        extra["line_cache_mb"] = LINE_CACHE_MB
        extra["line_cache"] = engine.line_cache.stats()
    bench_common.emit(
        metric,
        level("on", 16)["lines_per_sec"],
        "lines/s",
        level("off", 16)["lines_per_sec"],
        platform,
        lines_per_request=BATCH_LINES,
        batch_wait_ms=SWEEP_WAIT_MS,
        batch_max=SWEEP_BATCH_MAX,
        sweep=curve,
        batcher=batcher_stats,
        **extra,
    )


def stream_corpus(i: int) -> list[str]:
    rows = micro_batch(i, BATCH_LINES).split("\n")
    if REPEAT_RATIO is not None:
        # the repeat-template pool is all noise by construction, so a
        # --repeat-ratio corpus would never produce a detection and TTFD
        # would be undefined — overlay the plain path's detection cycle
        # (same ~2% density) on top of the repeat-heavy traffic
        for j in range(len(rows)):
            m = (i * 131 + j) % 97
            if m == 11:
                rows[j] = "java.lang.OutOfMemoryError: Java heap space"
            elif m == 13:
                rows[j] = "dial tcp 10.0.0.7:5432: Connection refused"
    return rows


def stream_main() -> None:
    metric = (
        f"stream_ttfd_p50_ms_{BATCH_LINES}line_chunk{CHUNK_LINES}"
        + metric_suffix()
    )
    platform = bench_common.require_tpu(metric, "ms")

    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets
    from log_parser_tpu.runtime import AnalysisEngine
    from log_parser_tpu.runtime.stream import StreamManager

    engine = AnalysisEngine(load_builtin_pattern_sets(), ScoringConfig())
    if LINE_CACHE_MB > 0:
        engine.enable_line_cache(LINE_CACHE_MB)
    mgr = StreamManager(engine, ttl_s=0, start_reaper=False)

    def chunks_of(rows: list[str]) -> list[bytes]:
        return [
            ("\n".join(rows[k : k + CHUNK_LINES]) + "\n").encode()
            for k in range(0, len(rows), CHUNK_LINES)
        ]

    n_chunks = (BATCH_LINES + CHUNK_LINES - 1) // CHUNK_LINES

    def run_blob(i: int) -> None:
        # blob mode can only fire once the whole tail has arrived: charge
        # the full replay window (every chunk at the fixed cadence) before
        # the one-shot analyze — that wait IS blob-mode end-to-end latency
        # under the same arrival process the sessions see
        if CHUNK_CADENCE_MS > 0:
            time.sleep(n_chunks * CHUNK_CADENCE_MS / 1e3)
        engine.analyze(
            PodFailureData(
                pod={"metadata": {"name": "stream"}},
                logs="\n".join(stream_corpus(i)),
            )
        )

    def run_stream(i: int) -> float | None:
        """Replay corpus ``i`` as a follow-mode session at the fixed chunk
        cadence; TTFD is first-byte-fed to first ``emit`` frame. Once the
        first detection is out the tail is moot for this metric, so the
        session closes (untimed) instead of draining the remaining
        chunks."""
        sess = mgr.open()
        ttfd_ms = None
        try:
            t0 = time.perf_counter()
            for chunk in chunks_of(stream_corpus(i)):
                if CHUNK_CADENCE_MS > 0:
                    time.sleep(CHUNK_CADENCE_MS / 1e3)
                frames = sess.feed(chunk)
                assert not any(f["type"] == "error" for f in frames), frames
                if any(f["type"] == "emit" for f in frames):
                    ttfd_ms = (time.perf_counter() - t0) * 1e3
                    break
        finally:
            sess.close()
        return ttfd_ms

    bounded = bench_common.bounded_runner(metric, "ms", platform)

    def warmup() -> None:
        # compile both shape families before timing: the blob-mode
        # full-corpus batch and the chunk-sized residual batches the
        # session feed path realizes
        for i in range(3):
            run_blob(i)
            run_stream(REQUESTS + i)

    bounded(warmup, bench_common.INIT_BUDGET_S, "warmup")

    blob_ms: list[float] = []
    ttfd_ms: list[float] = []
    misses = 0
    budget_s = max(bench_common.DRAIN_FLOOR_S, 10.0 * REQUESTS)

    def timed_blob() -> None:
        for i in range(3, REQUESTS + 3):
            t0 = time.perf_counter()
            run_blob(i)
            blob_ms.append((time.perf_counter() - t0) * 1e3)

    def timed_stream() -> None:
        nonlocal misses
        # offset index range: same line population and repeat-template
        # pool as the blob phase, but no request is byte-identical to one
        # the cache just served whole
        for i in range(REQUESTS + 3, 2 * REQUESTS + 3):
            t = run_stream(i)
            if t is None:
                misses += 1
            else:
                ttfd_ms.append(t)

    bounded(timed_blob, budget_s, "blob-mode baseline")
    bounded(timed_stream, budget_s, "stream ttfd")
    blob_ms.sort()
    ttfd_ms.sort()
    assert ttfd_ms, "no streaming session ever produced an emit frame"

    p50_ttfd = round(percentile(ttfd_ms, 0.50), 3)
    p50_blob = round(percentile(blob_ms, 0.50), 3)
    extra: dict[str, object] = {
        "n_requests": REQUESTS,
        "chunk_lines": CHUNK_LINES,
        "chunk_cadence_ms": CHUNK_CADENCE_MS,
        "ttfd_ms": {"p50": p50_ttfd, "p99": round(percentile(ttfd_ms, 0.99), 3)},
        "blob_ms": {"p50": p50_blob, "p99": round(percentile(blob_ms, 0.99), 3)},
        "ttfd_over_blob_p50": round(p50_ttfd / p50_blob, 4),
        "ttfd_misses": misses,
        "stream": mgr.stats(),
    }
    if REPEAT_RATIO is not None:
        extra["repeat_ratio"] = REPEAT_RATIO
    if engine.line_cache is not None:
        extra["line_cache_mb"] = LINE_CACHE_MB
        extra["line_cache"] = engine.line_cache.stats()
    bench_common.emit(metric, p50_ttfd, "ms", p50_blob, platform, **extra)


def main() -> None:
    if SWEEP:
        return sweep_main()
    if STREAM:
        return stream_main()
    suffix = "_http" if USE_HTTP else ""
    if CONCURRENCY > 1:
        suffix += f"_c{CONCURRENCY}"
    metric = (
        f"parse_latency_p99_ms_{BATCH_LINES}line_microbatch"
        + suffix
        + metric_suffix()
    )
    platform = bench_common.require_tpu(metric, "ms")

    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets
    from log_parser_tpu.runtime import AnalysisEngine

    engine = AnalysisEngine(load_builtin_pattern_sets(), ScoringConfig())
    if LINE_CACHE_MB > 0:
        engine.enable_line_cache(LINE_CACHE_MB)

    if USE_HTTP:
        import urllib.request

        from log_parser_tpu.serve.http import make_server

        server = make_server(engine, host="127.0.0.1", port=0)
        port = server.server_address[1]
        threading.Thread(target=server.serve_forever, daemon=True).start()

        def run_one(i: int) -> None:
            body = json.dumps(
                {"pod": {"metadata": {"name": "stream"}},
                 "logs": micro_batch(i, BATCH_LINES)}
            ).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/parse", data=body,
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req) as resp:
                assert resp.status == 200
                json.load(resp)
    else:
        def run_one(i: int) -> None:
            data = PodFailureData(
                pod={"metadata": {"name": "stream"}},
                logs=micro_batch(i, BATCH_LINES),
            )
            # the direct path must also go through the thread-safe entry
            # point when clients are concurrent: bare analyze() has no
            # internal locking and would race frequency state
            if CONCURRENCY > 1:
                engine.analyze_pipelined(data)
            else:
                engine.analyze(data)

    # EVERY phase — warmup, serial stream, concurrent fan-out — runs
    # through the shared wedge wrappers: a backend that stops returning
    # mid-request must yield a {"value": null} diagnostics exit, not an
    # rc=124 hang. Single-worker phases ride bounded_runner; the
    # concurrent fan-out uses run_bounded directly.
    bounded = bench_common.bounded_runner(metric, "ms", platform)

    def warmup() -> None:
        for i in range(3):  # compile every shape bucket the stream hits
            run_one(i)

    # warmup budget: the first compile set, before calling it a wedge
    bounded(warmup, bench_common.INIT_BUDGET_S, "warmup")

    lat: list[float] = []
    # measurement budget: a generous per-request ceiling times the whole
    # run — observed p99 is ~0.2 s/request, so 10 s/request only trips on
    # a genuinely wedged backend, never a slow-but-live one
    budget_s = max(bench_common.DRAIN_FLOOR_S, 10.0 * REQUESTS)
    if CONCURRENCY > 1:
        chunks = [list(range(c, REQUESTS, CONCURRENCY)) for c in range(CONCURRENCY)]
        per_thread: list[list[float]] = [[] for _ in range(CONCURRENCY)]

        def client(c: int):
            def inner() -> None:
                for i in chunks[c]:
                    t0 = time.perf_counter()
                    run_one(i)
                    per_thread[c].append((time.perf_counter() - t0) * 1e3)

            return inner

        bench_common.run_bounded(
            [client(c) for c in range(CONCURRENCY)],
            budget_s,
            metric,
            "ms",
            platform,
            "stream",
        )
        for vals in per_thread:
            lat.extend(vals)
    else:

        def serial() -> None:
            for i in range(REQUESTS):
                t0 = time.perf_counter()
                run_one(i)
                lat.append((time.perf_counter() - t0) * 1e3)

        bounded(serial, budget_s, "stream")
    lat.sort()

    # decompose request latency into engine phases (VERDICT r4 #7): the
    # HTTP share of p99 is (request p99 - engine-total p99), and
    # device_step_ms is the device dispatch+sync phase alone — without
    # this split an engine regression is indistinguishable from
    # transport cost in the artifact
    traces = list(engine.trace_history)[-REQUESTS:]
    phase_pcts: dict[str, object] = {}
    if traces:
        for name in ("device", "ingest", "finalize", "lock_wait"):
            vals = sorted(1e3 * t.as_dict().get(name, 0.0) for t in traces)
            phase_pcts[f"{name}_ms"] = {
                "p50": round(percentile(vals, 0.50), 3),
                "p99": round(percentile(vals, 0.99), 3),
            }
        totals = sorted(1e3 * t.total for t in traces)
        phase_pcts["engine_total_ms"] = {
            "p50": round(percentile(totals, 0.50), 3),
            "p99": round(percentile(totals, 0.99), 3),
        }
        # the trace deque is bounded (maxlen 512): when --requests
        # exceeds it, the phase stats cover only this tail window while
        # the headline p99 covers the whole run — say so in the artifact
        phase_pcts["phase_sample_n"] = len(traces)

    if REPEAT_RATIO is not None:
        phase_pcts["repeat_ratio"] = REPEAT_RATIO
    if engine.line_cache is not None:
        phase_pcts["line_cache_mb"] = LINE_CACHE_MB
        phase_pcts["line_cache"] = engine.line_cache.stats()
    bench_common.emit(
        metric,
        round(percentile(lat, 0.99), 3),
        "ms",
        round(percentile(lat, 0.50), 3),
        platform,
        n_requests=REQUESTS,
        **phase_pcts,
    )


if __name__ == "__main__":
    main()
