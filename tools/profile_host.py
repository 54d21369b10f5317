"""Host-phase profiler: the phases PERF.md §11 says bound the cached
ceiling, timed in isolation the way profile_fused.py isolates device
phases.

With the routing tier short-circuiting the match cube, a repeat-heavy
request's cost is host-side: ingest (blob → padded u8 batch), keying
(line → unique slot + content key; the cache's dedup, lookup and
populate, each timed a request), extraction (bits → MatchRecords),
assembly (unique rows → per-line bit matrix + override splice), and
finalize (records → scores + factor rows). Each phase is timed both as
the scalar reference path and (where one exists) the vectorized lane
that serves production, so a regression in either side is attributable
to one phase instead of "the request got slower".

The scalar reference lanes are pinned bit-identical to the vectorized
ones by tests/test_ingest_vec.py — this profiler measures, it does not
re-verify.

Usage:
    python tools/profile_host.py [--lines 200000] [--repeat-ratio 0.9]
                                 [--repeats 5]

Prints exactly one JSON line (wired into tools/refresh_artifacts.sh as
the ``profile_host_*`` artifact).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import statistics
import sys
import time

import numpy as np

# make the repo root importable without touching PYTHONPATH
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, n=5, warmup=1):
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts), statistics.median(ts)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lines", type=int, default=200_000)
    ap.add_argument(
        "--repeat-ratio",
        type=float,
        default=None,
        help="repeat-heavy corpus (bench_common.repeat_corpus) instead "
        "of bench.build_corpus's ~unique config-2 shape",
    )
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()

    import bench
    import bench_common

    import log_parser_tpu.native.ingest as ingest_mod
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.golden.javacompat import java_split_lines
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.ops.encode import encode_lines
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets
    from log_parser_tpu.runtime import AnalysisEngine
    from log_parser_tpu.runtime.finalize import finalize_batch
    from log_parser_tpu.runtime.linecache import (
        DEFAULT_LINE_CACHE_MB,
        LineCache,
        SlotHits,
        dedup_slots,
        records_from_hits,
        request_hits,
    )

    if args.repeat_ratio is not None:
        logs = bench_common.repeat_corpus(
            args.lines, args.repeat_ratio, "prof", random.Random(0xC0FFEE)
        )
    else:
        logs = bench.build_corpus(args.lines)

    report: dict = {
        "lines": args.lines,
        "repeat_ratio": args.repeat_ratio,
        "native_available": ingest_mod.get_lib() is not None,
    }

    # ---- ingest: scalar reference vs the vectorized Corpus fallback -----
    t_min, _ = timeit(
        lambda: encode_lines(java_split_lines(logs)), n=args.repeats
    )
    report["ingest_scalar_s"] = round(t_min, 4)
    real_get_lib = ingest_mod.get_lib
    ingest_mod.get_lib = lambda: None  # force the vectorized fallback
    try:
        t_min, _ = timeit(lambda: ingest_mod.Corpus(logs), n=args.repeats)
        report["ingest_vec_s"] = round(t_min, 4)
        corpus = ingest_mod.Corpus(logs)
    finally:
        ingest_mod.get_lib = real_get_lib
    enc = corpus.encoded
    report["batch_rows"], report["batch_cols"] = (int(x) for x in enc.u8.shape)

    # ---- keying: per-line dict loop vs the content-keyed dedup ---------
    def key_scalar():
        slot_of: dict[bytes, int] = {}
        line_slot = np.empty(corpus.n_lines, dtype=np.int64)
        for i in range(corpus.n_lines):
            lb = corpus.line_key_bytes(i)
            s = slot_of.get(lb)
            if s is None:
                s = len(slot_of)
                slot_of[lb] = s
            line_slot[i] = s
        return list(slot_of), line_slot

    t_min, _ = timeit(key_scalar, n=args.repeats)
    report["key_scalar_s"] = round(t_min, 4)
    t_min, _ = timeit(lambda: dedup_slots(corpus), n=args.repeats)
    report["key_vec_s"] = round(t_min, 4)
    line_slot, rep_lines, keys, counts = dedup_slots(corpus)
    report["unique_lines"] = int(rep_lines.size)

    sets = load_builtin_pattern_sets()
    engine = AnalysisEngine(sets, ScoringConfig())
    report["patterns"] = sum(len(s.patterns or []) for s in sets)

    # ---- the cached path's three keying steps, request by request: a
    # stream of distinct requests of this shape through one cache at the
    # serving budget, so lookups miss as served traffic's do and
    # populate evicts once the budget fills. The first two requests warm
    # the cache up; the rest are timed.
    cache = LineCache(
        engine.bank.n_columns, int(DEFAULT_LINE_CACHE_MB * 2**20)
    )
    steps: dict[str, list[float]] = {"dedup": [], "lookup": [], "populate": []}
    for r in range(args.repeats + 2):
        req = ingest_mod.Corpus(bench_common.repeat_corpus(
            args.lines, args.repeat_ratio or 0.0, f"prof{r}",
            random.Random(r),
        ))
        t0 = time.perf_counter()
        _, _, rkeys, rcounts = dedup_slots(req)
        t1 = time.perf_counter()
        found = cache.lookup(rkeys, rcounts)
        miss = np.flatnonzero(found.row < 0)
        t2 = time.perf_counter()
        cache.populate(
            rkeys.take(miss),
            np.zeros((miss.size, engine.bank.n_columns), dtype=bool),
        )
        t3 = time.perf_counter()
        if r >= 2:
            steps["dedup"].append(t1 - t0)
            steps["lookup"].append(t2 - t1)
            steps["populate"].append(t3 - t2)
    for name, ts in steps.items():
        report[f"key_{name}_s"] = round(statistics.median(ts), 4)
    report["line_cache"] = cache.stats()

    # ---- extract + assemble: the cache-hit serving path ------------------
    n = corpus.n_lines
    U = int(rep_lines.size)
    # the post-cache unique slots with no hits, as the cached path would
    # hold them for an all-miss line set (the sparse extract's cost
    # follows the hits; these times are its floor)
    hits = SlotHits(
        np.zeros(U, dtype=np.int64), np.zeros(U, dtype=np.int64),
        np.zeros(0, dtype=np.int64),
    )
    probe = engine.analyze(
        PodFailureData(pod={"metadata": {"name": "prof"}}, logs=logs)
    )
    assert probe.summary is not None
    fin_ref = engine.last_finalized

    def assemble():
        return request_hits(hits, line_slot, n)  # slots → (line, col)

    t_min, _ = timeit(assemble, n=args.repeats)
    report["assemble_s"] = round(t_min, 4)

    line, col = assemble()

    def extract():
        return records_from_hits(line, col, n, engine.bank, engine.tables)

    t_min, _ = timeit(extract, n=args.repeats)
    report["extract_s"] = round(t_min, 4)

    # ---- finalize: records → scores → factor rows ------------------------
    recs = engine._verify_approx(corpus, extract())
    freq_base = np.zeros(max(1, engine.bank.n_freq_slots), dtype=np.float64)
    freq_exists = np.zeros(max(1, engine.bank.n_freq_slots), dtype=bool)

    def finalize():
        return finalize_batch(
            engine.bank, engine.tables, engine.config, recs, n,
            freq_base, freq_exists,
        )

    t_min, _ = timeit(finalize, n=args.repeats)
    report["finalize_s"] = round(t_min, 4)

    if fin_ref is not None and len(fin_ref.scores):
        t_min, _ = timeit(
            lambda: fin_ref.factor_rows(engine.bank), n=args.repeats
        )
        report["factor_rows_s"] = round(t_min, 4)
        report["factor_rows_n"] = int(len(fin_ref.scores))

    report["host_total_scalar_s"] = round(
        report["ingest_scalar_s"] + report["key_scalar_s"], 4
    )
    report["host_total_vec_s"] = round(
        report["ingest_vec_s"] + report["key_vec_s"], 4
    )
    print(json.dumps(report))


if __name__ == "__main__":
    main()
