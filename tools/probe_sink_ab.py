"""Session-matched A/B of the Shift-Or stepper forms on the live
backend (the probe that decided the platform-split layout, PERF.md
§9d), all sharing the CURRENT bank's constants:

- v_ship:         the shipping stepper for this platform (TPU: bare
                  nh-carry hits; CPU: pair-composed sinks)
- v_perbyte_sink: per-byte sink update (only on a sink-layout bank)
- v_perbyte_hits: gate-free per-byte hits form on the current bank
- v_nosink_hits:  the bare 81-word layout rebuilt from scratch
- v_nosink_chain: bare layout + one 36-char chained literal (the
                  historical col-80 routing question)

Also times the bitglush shipping stepper alone so the cube split is
attributable in the same session. Prints one JSON line.

Usage: python tools/probe_sink_ab.py [--lines 200000] [--repeats 3]
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
    s = engine.matchers.shiftor
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
        "W": s.n_words,
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

    # -- v_ship: the shipping pair-composed sink stepper ----------------
    init, step, _fin = s.pair_stepper(B, lens)
    report["v_ship_s"] = round(timeit(scan_of(step, init), args.repeats), 4)

    # -- v_perbyte_sink: same sink semantics, one byte per update -------
    d0 = jnp.full((B, s.n_words), 0xFFFFFFFF, dtype=jnp.uint32)
    sc = s.start_clear[None, :]
    if s.sinks:
        not_sink = s.not_sink[None, :]

        def step_pb_sink(d, b1, b2, t):
            for b in (b1, b2):
                m = s._row_select(b)
                cand = (s._s1(d) & sc) | m
                d = cand & (d | not_sink)
            return d

        report["v_perbyte_sink_s"] = round(
            timeit(scan_of(step_pb_sink, d0), args.repeats), 4
        )

    # -- v_perbyte_hits: gate-free round-3 shape on the current bank ----
    e = s.end_mask[None, :]
    h0 = jnp.zeros((B, s.n_words), dtype=jnp.uint32)

    def step_pb_hits(carry, b1, b2, t):
        d, hits = carry
        for b in (b1, b2):
            m = s._row_select(b)
            d = (s._s1(d) & sc) | m
            hits = hits | ((~d) & e)
        return d, hits

    report["v_perbyte_hits_s"] = round(
        timeit(scan_of(step_pb_hits, (d0, h0)), args.repeats), 4
    )

    # -- v_nosink: round-3-shaped bank (alloc = m, no sink bits) --------
    import numpy as np

    bank = engine.matchers.bank
    flat = [
        (i, seq)
        for i in engine.matchers.shiftor_cols
        for seq in bank.columns[i].exact_seqs
    ]
    starts2: list[int] = []
    word_fill: list[int] = []
    for _, seq in flat:
        alloc = len(seq)
        if alloc > 32:
            w0 = len(word_fill)
            nw = (alloc + 31) // 32
            starts2.append(w0 * 32)
            word_fill.extend([32] * (nw - 1))
            word_fill.append(alloc - 32 * (nw - 1))
        else:
            w = next(
                (i for i, u in enumerate(word_fill) if u + alloc <= 32), None
            )
            if w is None:
                w = len(word_fill)
                word_fill.append(0)
            starts2.append(w * 32 + word_fill[w])
            word_fill[w] += alloc
    W2 = max(1, len(word_fill))
    mask2 = np.full((256, W2), 0xFFFFFFFF, dtype=np.uint32)
    sc2_np = np.full(W2, 0xFFFFFFFF, dtype=np.uint32)
    e2_np = np.zeros(W2, dtype=np.uint32)
    cont2 = np.zeros(W2, dtype=np.uint32)
    for (_, seq), g in zip(flat, starts2):
        sc2_np[g // 32] &= ~np.uint32(1 << (g % 32))
        for j, byteset in enumerate(seq):
            p = g + j
            bit = np.uint32(1 << (p % 32))
            for c in byteset:
                if c != 0:
                    mask2[c, p // 32] &= ~bit
        for w in range(g // 32 + 1, (g + len(seq) - 1) // 32 + 1):
            cont2[w] |= np.uint32(1)
        ee = g + len(seq) - 1
        e2_np[ee // 32] |= np.uint32(1 << (ee % 32))
    report["W_nosink"] = W2
    mask2_j = jnp.asarray(mask2)
    sc2_j = jnp.asarray(sc2_np)[None, :]
    e2_j = jnp.asarray(e2_np)[None, :]
    cont2_j = jnp.asarray(cont2)[None, :]
    has_chains2 = bool(cont2.any())
    d02 = jnp.full((B, W2), 0xFFFFFFFF, dtype=jnp.uint32)
    h02 = jnp.zeros((B, W2), dtype=jnp.uint32)

    def s1_2(x):
        sh = x << 1
        if has_chains2:
            carry = jnp.concatenate(
                [jnp.zeros_like(x[:, :1]), x[:, :-1] >> 31], axis=1
            )
            sh = sh | (carry & cont2_j)
        return sh

    def step_nosink(carry, b1, b2, t):
        d, hits = carry
        for b in (b1, b2):
            m = jnp.take(mask2_j, b.astype(jnp.int32), axis=0)
            d = (s1_2(d) & sc2_j) | m
            hits = hits | ((~d) & e2_j)
        return d, hits

    report["v_nosink_hits_s"] = round(
        timeit(scan_of(step_nosink, (d02, h02)), args.repeats), 4
    )

    # -- v_nosink_chain: same bank + one 36-char chained literal --------
    # (the col-80 routing question: what does turning the carry on for
    # the whole bank cost when a >32-bit literal joins it?)
    W3 = W2 + 2
    mask3 = np.pad(mask2, ((0, 0), (0, 2)), constant_values=0xFFFFFFFF)
    sc3 = np.pad(sc2_np, (0, 2), constant_values=0xFFFFFFFF)
    e3 = np.pad(e2_np, (0, 2))
    cont3 = np.pad(cont2, (0, 2))
    g0 = W2 * 32
    sc3[W2] &= ~np.uint32(1)
    lit = b"Back-off restarting failed container"
    for j, ch in enumerate(lit):
        p = g0 + j
        mask3[ch, p // 32] &= ~np.uint32(1 << (p % 32))
    cont3[W2 + 1] |= 1
    e3[(g0 + 35) // 32] |= np.uint32(1 << ((g0 + 35) % 32))
    mask3_j = jnp.asarray(mask3)
    sc3_j = jnp.asarray(sc3)[None, :]
    e3_j = jnp.asarray(e3)[None, :]
    cont3_j = jnp.asarray(cont3)[None, :]
    d03 = jnp.full((B, W3), 0xFFFFFFFF, dtype=jnp.uint32)
    h03 = jnp.zeros((B, W3), dtype=jnp.uint32)

    def s1_3(x):
        carry = jnp.concatenate(
            [jnp.zeros_like(x[:, :1]), x[:, :-1] >> 31], axis=1
        )
        return (x << 1) | (carry & cont3_j)

    def step_chain(carry, b1, b2, t):
        d, hits = carry
        for b in (b1, b2):
            m = jnp.take(mask3_j, b.astype(jnp.int32), axis=0)
            d = (s1_3(d) & sc3_j) | m
            hits = hits | ((~d) & e3_j)
        return d, hits

    report["v_nosink_chain_s"] = round(
        timeit(scan_of(step_chain, (d03, h03)), args.repeats), 4
    )

    # -- bitglush shipping stepper, same session ------------------------
    g = engine.matchers.bitglush
    if g is not None:
        gi, gstep, _gf = g.pair_stepper(B, lens)
        report["bitglush_ship_s"] = round(
            timeit(scan_of(gstep, gi), args.repeats), 4
        )
        report["bitglush_words"] = g.n_words

    print(json.dumps(report))


if __name__ == "__main__":
    main()
