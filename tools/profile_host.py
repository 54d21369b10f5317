"""Host-phase profiler: the phases PERF.md §11 says bound the cached
ceiling, timed in isolation the way profile_fused.py isolates device
phases.

With the routing tier short-circuiting the match cube, a repeat-heavy
request's cost is host-side: ingest (blob → padded u8 batch), keying
(line → unique slot + digest), extraction (bits → MatchRecords),
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
        KeyInterner,
        SlotHits,
        dedup_slots,
        line_key,
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

    # ---- keying: per-line dict loop vs lexsort dedup ---------------------
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
        return [line_key(lb) for lb in slot_of], line_slot

    t_min, _ = timeit(key_scalar, n=args.repeats)
    report["key_scalar_s"] = round(t_min, 4)
    t_min, _ = timeit(lambda: dedup_slots(corpus), n=args.repeats)
    report["key_vec_s"] = round(t_min, 4)
    # two-level keying: warm interner turns the per-unique-line blake2b
    # into a vectorized probe64 + memcmp verify (first touch paid once in
    # the warmup pass), the serving shape for repeat-heavy traffic
    interner = KeyInterner()
    dedup_slots(corpus, interner=interner)  # first touch: populate
    t_min, _ = timeit(
        lambda: dedup_slots(corpus, interner=interner), n=args.repeats
    )
    report["key_vec_interned_s"] = round(t_min, 4)
    report["interner"] = interner.stats()
    line_slot, rep_lines, keys, counts = dedup_slots(corpus)
    report["unique_lines"] = len(keys)

    # the digest sub-phase in isolation (the part the interner replaces;
    # the lexsort dedup above it is shared by both lanes): per-unique
    # blake2b vs warm probe64+verify digest recovery
    kv = corpus.key_view()
    blob, starts, ends = kv
    nl = corpus.n_lines
    starts, ends = starts[:nl], ends[:nl]
    width = corpus.encoded.u8.shape[1]
    lengths = (ends - starts).astype(np.int64)
    kw = -(-(width + 8) // 8) * 8
    km = np.zeros((nl, kw), dtype=np.uint8)
    km[:, :width] = corpus.encoded.u8[:nl]
    km[:, width : width + 8] = (
        lengths.astype("<i8").reshape(nl, 1).view(np.uint8)
    )
    v64 = km.view("<i8")
    s_l = starts[rep_lines].tolist()
    e_l = ends[rep_lines].tolist()
    t_min, _ = timeit(
        lambda: [line_key(blob[a:b]) for a, b in zip(s_l, e_l)],
        n=args.repeats,
    )
    report["digest_blake2b_s"] = round(t_min, 4)
    t_min, _ = timeit(
        lambda: interner.digests(
            v64[rep_lines], lengths[rep_lines], width, blob, s_l, e_l
        ),
        n=args.repeats,
    )
    report["digest_interned_s"] = round(t_min, 4)

    # ---- extract + assemble: the cache-hit serving path ------------------
    sets = load_builtin_pattern_sets()
    engine = AnalysisEngine(sets, ScoringConfig())
    report["patterns"] = sum(len(s.patterns or []) for s in sets)
    n = corpus.n_lines
    U = len(keys)
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
    report["host_total_interned_s"] = round(
        report["ingest_vec_s"] + report["key_vec_interned_s"], 4
    )
    print(json.dumps(report))


if __name__ == "__main__":
    main()
