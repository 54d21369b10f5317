"""Timing probe that decided the first-fit chainless bitglush layout
(PERF.md §9d: carry removal measured 0.162 -> 0.064 s on v5e; the
shipping stepper has been chainless since). Still useful for width
sensitivity on the live backend:

- v_ship:        the shipping stepper (now first-fit, carry-free on
                 chainless banks)
- v_nocarry:     the synthetic carry-free form at the bank's width
                 (≈ v_ship on a chainless bank — the historical A/B)
- v_nocarry_w:   same ops at a padded width (fragmentation estimate,
                 default 112 words)

Usage: python tools/probe_chainless.py [--lines 200000] [--width 112]
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
    ap.add_argument("--width", type=int, default=112)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.native.ingest import Corpus
    from log_parser_tpu.ops.match import pack_byte_pairs
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets
    from log_parser_tpu.runtime import AnalysisEngine

    engine = AnalysisEngine(load_builtin_pattern_sets(), ScoringConfig())
    g = engine.matchers.bitglush
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
        "W": g.n_words,
        "max_skip_run": g.max_skip_run,
    }

    def scan_of(step, init):
        @jax.jit
        def run(lines_tb, lens):
            pairs, ts = pack_byte_pairs(lines_tb)
            out, _ = jax.lax.scan(
                lambda c, xs: (step(c, xs[0][0], xs[0][1], xs[1]), None),
                init,
                (pairs, ts),
            )
            return out

        return lambda: jax.block_until_ready(run(lines_tb, lens))

    gi, gstep, _gf = g.pair_stepper(B, lens)
    report["v_ship_s"] = round(timeit(scan_of(gstep, gi), args.repeats), 4)

    def chainless_stepper(W, bmask, s_all, s, k, ss):
        # mirrors the shipping (guard-bit, carry-free) sink stepper
        init = (jnp.zeros((B, W), jnp.uint32), jnp.zeros((B,), bool))

        def one(d, pw, b, pos):
            c = (d << 1) | jnp.where(pos == 0, s_all, s)
            for _ in range(g.max_skip_run):
                c = c | ((c & k) << 1)
            brow = jnp.take(bmask, b.astype(jnp.int32), axis=0)
            return brow & (c | (d & ss)), pw

        def step(carry, b1, b2, t):
            d, pw = carry
            p0 = 2 * t
            d, pw = one(d, pw, b1, p0)
            d, pw = one(d, pw, b2, p0 + 1)
            return (d, pw)

        return init, step

    # same width, no carry
    init, step = chainless_stepper(
        g.n_words, g.bmask, g.start_all, g.start, g.k_skip, g.s_static
    )
    report["v_nocarry_s"] = round(timeit(scan_of(step, init), args.repeats), 4)

    # padded width, no carry (first-fit fragmentation estimate)
    Wp = args.width
    pad = Wp - g.n_words
    if pad > 0:
        bm = jnp.asarray(
            np.pad(np.asarray(g.bmask), ((0, 0), (0, pad)))
        )
        padv = lambda a: jnp.asarray(  # noqa: E731
            np.pad(np.asarray(a), (0, pad))
        )
        init, step = chainless_stepper(
            Wp, bm, padv(g.start_all), padv(g.start),
            padv(g.k_skip), padv(g.s_static),
        )
        report["v_nocarry_wide_s"] = round(
            timeit(scan_of(step, init), args.repeats), 4
        )
        report["wide_W"] = Wp

    print(json.dumps(report))


if __name__ == "__main__":
    main()
