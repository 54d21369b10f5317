"""Phase/subcomponent profiler for the fused device program (VERDICT r2 #2).

Times, on the current JAX platform:

- corpus ingest (host) and input upload (host->device transfer);
- the match cube alone vs the full fused step (cube + factor extraction +
  record compaction) — the difference is the extraction/compaction cost;
- output readback (device->host transfer of the record buffers) —
  isolates the per-request transfer floor;
- pair-stride (2 bytes/step) vs single-stride (1 byte/step) DFA scans;
- engine.analyze() end-to-end with the PhaseTrace breakdown.

Usage:
    python tools/profile_fused.py [--lines 200000] [--synthetic-patterns 0]
                                  [--trace /tmp/jaxtrace]

With --synthetic-patterns N, a generated N-regex library (bench_bank's
shape) replaces the builtin one.  With --trace DIR, the steady-state
analyze() runs under jax.profiler.trace for TensorBoard/xprof reading.
"""

from __future__ import annotations

import argparse
import json
import os
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


def build_corpus(n: int) -> str:
    import bench

    return bench.build_corpus(n)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lines", type=int, default=200_000)
    ap.add_argument("--synthetic-patterns", type=int, default=0)
    ap.add_argument("--trace", default=None)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.native.ingest import Corpus
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.runtime import AnalysisEngine

    report: dict = {"platform": jax.devices()[0].platform, "lines": args.lines}

    if args.synthetic_patterns:
        import bench_bank

        sets = [bench_bank.synth_library(args.synthetic_patterns)]
        report["patterns"] = args.synthetic_patterns
    else:
        from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets

        sets = load_builtin_pattern_sets()
        report["patterns"] = sum(len(s.patterns or []) for s in sets)

    logs = build_corpus(args.lines)
    engine = AnalysisEngine(sets, ScoringConfig())
    data = PodFailureData(pod={"metadata": {"name": "prof"}}, logs=logs)

    # ---- ingest ---------------------------------------------------------
    t_min, t_med = timeit(lambda: Corpus(logs), n=args.repeats)
    report["ingest_s"] = round(t_min, 4)
    corpus = Corpus(logs)
    enc = corpus.encoded
    B, T = enc.u8.shape
    report["batch_rows"] = B
    report["batch_cols"] = T

    # ---- input upload ---------------------------------------------------
    def upload():
        jax.block_until_ready(jax.device_put(enc.u8))

    t_min, _ = timeit(upload, n=args.repeats)
    report["upload_s"] = round(t_min, 4)
    report["upload_mb"] = round(enc.u8.nbytes / 1e6, 1)

    # ---- cube alone vs full step ---------------------------------------
    matchers = engine.matchers
    report["tiers"] = {
        "dfa_cols": len(matchers.dfa_cols),
        "shiftor_cols": len(matchers.shiftor_cols),
        "bitglush_cols": len(matchers.bitglush_cols),
        "bitglush_words": matchers.bitglush.n_words if matchers.bitglush else 0,
        "multi_groups": len(matchers.multi_groups),
        "multi_cols": len(matchers.multi_cols),
        "prefilter_cols": len(matchers.prefilter_cols),
        "host_cols": len(matchers.host_cols),
    }
    lines_tb = jnp.asarray(enc.u8.T)
    lens = jnp.asarray(enc.lengths)
    jax.block_until_ready((lines_tb, lens))

    cube_jit = jax.jit(lambda lt, ln: matchers.cube(lt, ln))

    def run_cube():
        jax.block_until_ready(cube_jit(lines_tb, lens))

    t_min, _ = timeit(run_cube, n=args.repeats)
    report["cube_s"] = round(t_min, 4)

    fused = engine.fused
    ladder, _cap = fused.k_ladder(enc.u8, engine._k_hint)
    K = ladder[0]
    report["k_bucket"] = K

    def run_step_nosync():
        return fused.dispatch(K, enc.u8, enc.lengths, corpus.n_lines)

    def run_step():
        jax.block_until_ready(run_step_nosync())

    t_min, _ = timeit(run_step, n=args.repeats)
    report["fused_step_s"] = round(t_min, 4)

    # ---- output readback (the per-request transfer floor) ---------------
    out = run_step_nosync()
    jax.block_until_ready(out)
    out_arrays = out if isinstance(out, (tuple, list)) else (out,)

    def readback():
        for o in out_arrays:
            np.asarray(o)

    t_min, _ = timeit(readback, n=args.repeats)
    report["readback_s"] = round(t_min, 4)
    report["readback_arrays"] = len(out_arrays)
    report["readback_kb"] = round(
        sum(np.asarray(o).nbytes for o in out_arrays) / 1e3, 1
    )

    # ---- stride A/B -----------------------------------------------------
    m1 = MatcherBanks(engine.bank, stride=1)
    cube1_jit = jax.jit(lambda lt, ln: m1.cube(lt, ln))

    def run_cube1():
        jax.block_until_ready(cube1_jit(lines_tb, lens))

    t_min, _ = timeit(run_cube1, n=args.repeats)
    report["cube_stride1_s"] = round(t_min, 4)

    # ---- end-to-end analyze with phase trace ----------------------------
    engine.analyze(data)  # warm

    def run_analyze():
        engine.analyze(data)

    if args.trace:
        with jax.profiler.trace(args.trace):
            run_analyze()
        report["trace_dir"] = args.trace
    t_min, _ = timeit(run_analyze, n=max(2, args.repeats - 2))
    report["analyze_s"] = round(t_min, 4)
    report["analyze_lines_per_s"] = round(args.lines / t_min, 1)
    report["phases_s"] = {
        k: round(v, 4) for k, v in (engine.last_trace.as_dict() or {}).items()
    }

    print(json.dumps(report))


if __name__ == "__main__":
    main()
