"""Isolate per-tier cube cost: run each matcher tier's stepper alone in
its own scan over the config-2 corpus, plus the full fused cube, so the
cube's time can be attributed (PERF.md §1 methodology).

Usage: python tools/probe_tiers.py [--lines 200000] [--repeats 3]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench_common import timeit  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lines", type=int, default=200_000)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import bench
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.native.ingest import Corpus
    from log_parser_tpu.ops.match import pack_byte_pairs
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets
    from log_parser_tpu.runtime import AnalysisEngine

    engine = AnalysisEngine(load_builtin_pattern_sets(), ScoringConfig())
    m = engine.matchers
    corpus = Corpus(bench.build_corpus(args.lines))
    enc = corpus.encoded
    lines_tb = jnp.asarray(enc.u8.T)
    lens = jnp.asarray(enc.lengths)
    jax.block_until_ready((lines_tb, lens))
    B = int(lens.shape[0])
    report = {
        "platform": jax.devices()[0].platform,
        "rows": B,
        "T": int(lines_tb.shape[0]),
    }

    def scan_only(stepper_fns):
        """Compile ONE scan advancing the given steppers' carries."""
        inits = tuple(s[0] for s in stepper_fns)

        @jax.jit
        def run(lines_tb, lens):
            pairs, ts = pack_byte_pairs(lines_tb)

            def step(carries, xs):
                pair, t = xs
                return tuple(
                    s[1](c, pair[0], pair[1], t)
                    for s, c in zip(stepper_fns, carries)
                ), None

            finals, _ = jax.lax.scan(step, inits, (pairs, ts))
            return finals

        return lambda: jax.block_until_ready(run(lines_tb, lens))

    # each multi-DFA group alone, then all groups, then shiftor, then all
    for gi, g in enumerate(m.multi_groups):
        fn = scan_only([g.pair_stepper(B, lens)])
        report[f"multi_g{gi}_s"] = round(timeit(fn, n=args.repeats), 4)
        report[f"multi_g{gi}_states"] = g.n_states
    if m.multi_groups:
        fn = scan_only([g.pair_stepper(B, lens) for g in m.multi_groups])
        report["multi_separate_s"] = round(timeit(fn, n=args.repeats), 4)
    if m.shiftor is not None:
        fn = scan_only([m.shiftor.pair_stepper(B, lens)])
        report["shiftor_s"] = round(timeit(fn, n=args.repeats), 4)
        report["shiftor_words"] = m.shiftor.n_words
    if m.bitglush is not None:
        fn = scan_only([m.bitglush.pair_stepper(B, lens)])
        report["bitglush_s"] = round(timeit(fn, n=args.repeats), 4)
        report["bitglush_words"] = m.bitglush.n_words

    cube_jit = jax.jit(m.cube)
    full = lambda: jax.block_until_ready(cube_jit(lines_tb, lens))
    report["cube_s"] = round(timeit(full, n=args.repeats), 4)

    if m.multi_cluster is not None:
        fn = scan_only([m.multi_cluster.pair_stepper(B, lens)])
        report["multi_cluster_s"] = round(timeit(fn, n=args.repeats), 4)

    print(json.dumps(report))


if __name__ == "__main__":
    main()
