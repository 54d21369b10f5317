"""Session-matched A/B of EVERY Pallas kernel tier against its XLA scan
baseline, one structured JSON verdict for both (supersedes the
bitglush-only tools/probe_pallas_ab.py).

Tiers covered:

- ``bitglush``  — ops/bitglush_pallas.py vs the chainless pair stepper
  in one lax.scan (exact probe_tiers.py methodology).  PERF.md §9 owns
  the standing decision rule, encoded in the verdict below: on a LIVE
  TPU, ``pallas_over_xla >= ~1`` means the kernel loses its re-trial
  and gets deleted with a recorded negative.
- ``multidfa``  — ops/matchdfa_pallas.py (union-DFA scan, MXU one-hot
  planes instead of the scalar-unit gather) vs the gate-free
  pair_stepper lax.scan the cube runs when the kernel is off.  Where
  the tier plan leaves no union groups (the bit tier took the columns,
  or no native union builder) the probe rebuilds them through the
  Python construction so the A/B still runs.

Both comparisons are bit-exact or the probe says so loudly
(``verdict: parity_failure`` trumps any timing).  On a non-TPU backend
the kernels run in interpreter mode: parity is meaningful, timing is
not, and the verdict pins ``pending_live_tpu`` — so the default shape
shrinks to keep the interpreter walk honest but fast.

Run on the chip (one process holds it):

    python tools/probe_kernels.py

Four compiles total (one per variant per tier).
Prints one JSON line: per-tier times, bit-equality, ``pallas_over_xla``
ratios, and a ``verdicts`` block.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench_common import timeit  # noqa: E402

# PERF.md §9: delete the bitglush kernel if the live-TPU ratio comes
# back >= ~1 (the kernel must BEAT the scan path to earn default
# status; parity already lost the re-trial)
BITGLUSH_DELETE_THRESHOLD = 1.0


def _verdict(tier: dict, platform: str, *, delete_at: float | None) -> str:
    if "skipped" in tier:
        return "not_measured"
    if not tier.get("bit_equal", False):
        return "parity_failure"
    if platform != "tpu":
        return "pending_live_tpu"
    ratio = tier["pallas_over_xla"]
    if delete_at is not None:
        return "delete_kernel" if ratio >= delete_at else "keep_kernel"
    return "promote_candidate" if ratio < 1.0 else "keep_off"


def _probe_bitglush(bank, lines_tb, lens, repeats: int) -> dict:
    import jax
    import numpy as np

    from log_parser_tpu.ops.bitglush_pallas import (
        bitglush_hits_pallas,
        pick_tile,
    )
    from log_parser_tpu.ops.match import pack_byte_pairs

    if bank is None:
        return {"skipped": "no bitglush bank under the current tier "
                           "policy (PERF.md §9g)"}
    B = int(lens.shape[0])
    if pick_tile(B) is None:
        return {"skipped": f"no valid pallas tile for B={B}"}
    tier = {
        "n_words": bank.n_words,
        "has_chains": bool(bank.has_chains),
        "use_sinks": bool(bank.use_sinks),
    }

    stepper = bank.pair_stepper(B, lens)

    @jax.jit
    def xla_scan(lines_tb, lens):
        pairs, ts = pack_byte_pairs(lines_tb)

        def step(carry, xs):
            pair, t = xs
            return stepper[1](carry, pair[0], pair[1], t), None

        final, _ = jax.lax.scan(step, stepper[0], (pairs, ts))
        return final

    out = xla_scan(lines_tb, lens)
    jax.block_until_ready(out)
    tier["xla_s"] = round(
        timeit(lambda: jax.block_until_ready(xla_scan(lines_tb, lens)),
               n=repeats), 4
    )

    @jax.jit
    def pallas_scan(lines_tb, lens):
        return bitglush_hits_pallas(bank, lines_tb, lens)

    phits = pallas_scan(lines_tb, lens)
    jax.block_until_ready(phits)
    tier["pallas_s"] = round(
        timeit(lambda: jax.block_until_ready(pallas_scan(lines_tb, lens)),
               n=repeats), 4
    )
    # carry layouts differ (the scan may run in sink mode), so parity
    # goes through the bank's own column readers
    cols_xla = np.asarray(stepper[2](out))
    cols_pallas = np.asarray(bank.columns_from_hits(phits))
    tier["bit_equal"] = bool(np.array_equal(cols_xla, cols_pallas))
    tier["pallas_over_xla"] = round(tier["pallas_s"] / tier["xla_s"], 3)
    return tier


# re-pack cap when the bank's own groups (MULTI_STATE_BUDGET = 8192
# states) fail kernel admission: 2048 states pads to lane-aligned
# planes well inside the 12 MB budget at the full 128-row tile, so the
# A/B measures the kernel on groups it would actually admit
REPACK_MAX_STATES = 2048


def _union_groups(matchers, max_states: int | None = None):
    """The engine's union groups plus their per-group entries (the
    admission planner needs entries to re-split oversized groups); where
    the tier plan left them empty (the bit tier took the columns, or no
    native builder), or when a ``max_states`` re-pack is requested,
    rebuild through the Python union construction over the same regex
    columns so the kernel A/B runs."""
    if max_states is None and matchers.multi_groups:
        return (
            matchers.multi_groups,
            getattr(matchers, "_multi_entries", None) or None,
            False,
        )
    from log_parser_tpu.ops.match import MatcherBanks, MultiDfaBank
    from log_parser_tpu.patterns.regex.multidfa import pack_union_groups

    entries = [
        (i, c.regex, c.case_insensitive)
        for i, c in enumerate(matchers.bank.columns)
        if getattr(c, "regex", None)
    ]
    if not entries:
        return [], None, False
    groups, _rejected = pack_union_groups(
        entries,
        max_states=max_states or MatcherBanks.MULTI_STATE_BUDGET,
        max_group=MatcherBanks.MULTI_MAX_GROUP,
    )
    emap = {e[0]: e for e in entries}
    return (
        [MultiDfaBank(md, keys) for keys, md in groups],
        [[emap[k] for k in keys] for keys, _ in groups],
        True,
    )


def _probe_multidfa(matchers, lines_tb, lens, repeats: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from log_parser_tpu.ops.match import pack_byte_pairs
    from log_parser_tpu.ops.matchdfa_pallas import (
        build_dfa_plan,
        dfa_tile,
        multidfa_reported_pallas,
    )

    from log_parser_tpu.ops.match import MatcherBanks

    groups, group_entries, forced = _union_groups(matchers)
    if not groups:
        return {"skipped": "no union groups (no regex columns to pack)"}
    plan, reason = build_dfa_plan(
        groups,
        entries=group_entries,
        max_states=MatcherBanks.MULTI_STATE_BUDGET,
    )
    repacked = None
    if plan is None and reason == "table_too_large":
        # admission failed even with the entry-level re-split (or no
        # entries survived to split on) — re-pack tighter as a backstop
        # so the kernel is still measured on admissible groups
        groups, group_entries, forced = _union_groups(
            matchers, REPACK_MAX_STATES
        )
        if groups:
            plan, reason = build_dfa_plan(groups, entries=group_entries)
            repacked = REPACK_MAX_STATES
    if plan is None:
        return {"skipped": f"kernel admission refused: {reason}"}
    # the plan may have re-split groups for admission — the XLA baseline
    # must scan the SAME automata the kernel runs, so adopt plan.groups
    groups = list(plan.groups)
    B = int(lens.shape[0])
    T = int(lines_tb.shape[0])
    tile = dfa_tile(plan, B, T)
    if tile is None:
        return {"skipped": f"no valid batch tile for B={B} at T={T}"}
    tier = {
        "n_groups": plan.n_groups,
        "s_pad": plan.s_pad,
        "tile_b": tile,
        "admission_reason": reason,
        "geometry": plan.geometry,
        "forced_python_union": forced,
        "repacked_max_states": repacked,
    }

    steppers = [g.pair_stepper(B, lens) for g in groups]

    @jax.jit
    def xla_scan(lines_tb, lens):
        pairs, ts = pack_byte_pairs(lines_tb)

        def step(carries, xs):
            pair, t = xs
            return [
                st[1](c, pair[0], pair[1], t)
                for st, c in zip(steppers, carries)
            ], None

        finals, _ = jax.lax.scan(
            step, [st[0] for st in steppers], (pairs, ts)
        )
        return jnp.stack(
            [st[2](f)[1] for st, f in zip(steppers, finals)], axis=1
        ).astype(jnp.int32)

    out = xla_scan(lines_tb, lens)
    jax.block_until_ready(out)
    tier["xla_s"] = round(
        timeit(lambda: jax.block_until_ready(xla_scan(lines_tb, lens)),
               n=repeats), 4
    )

    @jax.jit
    def pallas_scan(lines_tb):
        return multidfa_reported_pallas(plan, lines_tb)

    prep = pallas_scan(lines_tb)
    jax.block_until_ready(prep)
    tier["pallas_s"] = round(
        timeit(lambda: jax.block_until_ready(pallas_scan(lines_tb)),
               n=repeats), 4
    )
    tier["bit_equal"] = bool(
        np.array_equal(np.asarray(out) != 0, np.asarray(prep) != 0)
    )
    tier["pallas_over_xla"] = round(tier["pallas_s"] / tier["xla_s"], 3)
    return tier


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lines", type=int, default=None,
                    help="corpus lines (default: 200000 on tpu, 2000 "
                         "elsewhere — interpreter-mode kernels are for "
                         "parity, not timing)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--tier", choices=("bitglush", "multidfa", "all"),
                    default="all")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import bench
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.native.ingest import Corpus
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets
    from log_parser_tpu.runtime import AnalysisEngine

    platform = jax.devices()[0].platform
    n_lines = args.lines if args.lines is not None else (
        200_000 if platform == "tpu" else 2_000
    )

    engine = AnalysisEngine(load_builtin_pattern_sets(), ScoringConfig())
    corpus = Corpus(bench.build_corpus(n_lines))
    enc = corpus.encoded
    lines_tb = jnp.asarray(enc.u8.T)
    lens = jnp.asarray(enc.lengths)
    jax.block_until_ready((lines_tb, lens))

    report = {
        "platform": platform,
        "rows": int(lens.shape[0]),
        "T": int(lines_tb.shape[0]),
        "tiers": {},
    }
    if args.tier in ("bitglush", "all"):
        report["tiers"]["bitglush"] = _probe_bitglush(
            engine.matchers.bitglush, lines_tb, lens, args.repeats
        )
    if args.tier in ("multidfa", "all"):
        report["tiers"]["multidfa"] = _probe_multidfa(
            engine.matchers, lines_tb, lens, args.repeats
        )

    report["verdicts"] = {
        name: _verdict(
            tier, platform,
            delete_at=BITGLUSH_DELETE_THRESHOLD
            if name == "bitglush" else None,
        )
        for name, tier in report["tiers"].items()
    }
    print(json.dumps(report))
    if any(v == "parity_failure" for v in report["verdicts"].values()):
        sys.exit(2)


if __name__ == "__main__":
    main()
