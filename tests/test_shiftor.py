"""Bit-parallel Shift-Or matcher: packing, exactness vs host re, and the
adaptive tier split."""

from __future__ import annotations

import random
import re

import numpy as np
import pytest

from log_parser_tpu.golden.javacompat import compile_java_regex
from log_parser_tpu.ops.encode import encode_lines
from log_parser_tpu.ops.match import MatcherBanks
from log_parser_tpu.ops.shiftor import ShiftOrBank
from log_parser_tpu.patterns.regex import parse_java_regex
from log_parser_tpu.patterns.regex.literals import exact_sequences


REGEXES = [
    "OutOfMemoryError",
    "Connection refused",
    "(GC|gc) overhead",
    "x(code|status)=[45]\\d\\d",
    "a{3}b",
    "[Tt]imeout",
]


def _bank_for(regexes: list[str]) -> tuple[ShiftOrBank, list[re.Pattern]]:
    entries = []
    hosts = []
    for i, rx in enumerate(regexes):
        seqs = exact_sequences(parse_java_regex(rx, False))
        assert seqs is not None, rx
        entries.append((i, seqs))
        hosts.append(compile_java_regex(rx))
    return ShiftOrBank(entries), hosts


def test_exactness_vs_host_re():
    bank, hosts = _bank_for(REGEXES)
    rng = random.Random(11)
    alphabet = "aAbx45 GCgcOutfMemoryErrConnectionRefusedTimeoutcodestatus=d019"
    lines = [
        "".join(rng.choice(alphabet) for _ in range(rng.randrange(0, 60)))
        for _ in range(256)
    ]
    # plant guaranteed positives
    lines += [
        "java.lang.OutOfMemoryError: heap",
        "dial tcp: Connection refused",
        "gc overhead limit",
        "xstatus=503 from upstream",
        "aaab here",
        "Timeout after 3s",
        "xcode=99",  # negative: [45] required
        "aab",  # negative: needs aaa
    ]
    enc = encode_lines(lines)
    got = np.asarray(
        bank._run(np.asarray(enc.u8.T), np.asarray(enc.lengths))
    )
    for i, host in enumerate(hosts):
        expect = np.zeros(len(lines), dtype=bool)
        for j, line in enumerate(lines):
            expect[j] = bool(host.search(line))
        np.testing.assert_array_equal(
            got[: len(lines), i], expect, err_msg=REGEXES[i]
        )


def _check_exact(regexes: list[str], lines: list[str]):
    bank, hosts = _bank_for(regexes)
    enc = encode_lines(lines)
    got = np.asarray(bank._run(np.asarray(enc.u8.T), np.asarray(enc.lengths)))
    for i, host in enumerate(hosts):
        for j, line in enumerate(lines):
            want = bool(host.search(line))
            assert bool(got[j, i]) == want, (regexes[i], line)


FULL_WIDTH = (
    ["Error", "ab"],
    [
        "x" * 27 + "Error",      # 32 chars, completion at byte 31
        "x" * 59 + "Error",      # 64 chars, completion at byte 63
        "x" * 26 + "Error" + "y",  # completion one byte before the end
        "x" * 30 + "ab",         # 2-seq completion at full width
        "x" * 31 + "a",          # suffix is only a prefix of the seq
        "Error" + "z" * 27,      # completion early in a full-width row
        "",
    ],
)
ONE_BYTE = (["q", "[0-9]"], ["q", "zq", "3", "zzz3", "none", ""])
S31 = "abcdefghijklmnopqrstuvwxyz01234"
S32 = S31 + "5"
LEN_31_32 = (
    [S31, S32],
    [
        S31, S32, "x" + S31, "xy" + S31, S31[:-1],
        "x" * 30 + S32, S32 + "tail", S32[1:],
    ],
)
S62 = "A fatal error has been detected by the Java Runtime Environmen"
LONG_CHAIN = (
    [S62],
    [S62, "x" + S62 + "y", S62[:-1] + "X", "pad " * 8 + S62, ""],
)


def test_sink_full_width_lines():
    """Completions at the scan's very last byte — lines that exactly
    fill the padded width (multiples of 32) — must still count."""
    _check_exact(*FULL_WIDTH)


def test_sink_one_byte_sequences():
    """m=1 sequences: start == end."""
    _check_exact(*ONE_BYTE)


def test_sink_31_32_length_sequences_chain():
    """Lengths 31 and 32 fill a word each without a chain; exactness
    must hold at the word's top bit."""
    bank, _ = _bank_for([S31, S32])
    assert not bank.has_chains and bank.n_words == 2
    _check_exact(*LEN_31_32)


def test_sink_long_chain_sequences():
    """>32-length sequences (multi-word chains): carries cross two word
    boundaries."""
    bank, _ = _bank_for([S62])
    assert bank.has_chains
    _check_exact(*LONG_CHAIN)


@pytest.mark.parametrize(
    "regexes,lines",
    [FULL_WIDTH, ONE_BYTE, LEN_31_32, LONG_CHAIN],
    ids=["full_width", "one_byte", "len_31_32", "long_chain"],
)
def test_shiftor_exact_through_matcher_banks(regexes, lines):
    """The same cases through ``MatcherBanks.cube`` under the default
    tier plan: Shift-Or inside the fused scan it runs in on the chip.
    Each regex is a sequence event (an exact role), so long literals too
    ride Shift-Or's chains rather than the truncating bit tier."""
    import jax.numpy as jnp

    from helpers import make_pattern, make_pattern_set
    from log_parser_tpu.patterns.bank import PatternBank

    bank = PatternBank([make_pattern_set([
        make_pattern("p0", regex="zzz-no-such-line", confidence=0.5,
                     sequences=[(1.5, list(regexes))]),
    ])])
    mb = MatcherBanks(bank)
    cols = [
        next(i for i, c in enumerate(bank.columns) if c.regex == rx)
        for rx in regexes
    ]
    assert set(cols) <= set(mb.shiftor_cols)
    enc = encode_lines(lines)
    got = np.asarray(
        mb.cube(jnp.asarray(enc.u8.T), jnp.asarray(enc.lengths))
    )
    for rx, col in zip(regexes, cols):
        host = compile_java_regex(rx)
        want = [bool(host.search(ln)) for ln in lines]
        np.testing.assert_array_equal(got[: len(lines), col], want, err_msg=rx)


def test_word_packing_isolates_neighbors():
    """Sequences packed into one word must not leak shift bits into each
    other: 'ab' and 'ba' share a word; 'aba' contains both, 'aa' neither."""
    bank, _ = _bank_for(["ab", "ba"])
    assert bank.n_words == 1
    enc = encode_lines(["aba", "aa", "ab", "ba", ""])
    got = np.asarray(bank._run(np.asarray(enc.u8.T), np.asarray(enc.lengths)))
    np.testing.assert_array_equal(
        got[:5], [[True, True], [False, False], [True, False], [False, True], [False, False]]
    )


def test_cross_word_chain_sequences():
    """Sequences longer than 32 positions span words via the carry chain
    (cont_mask): exactness at every boundary-straddling offset, no leak
    into co-packed short sequences, correct restart mid-line."""
    long_a = "A fatal error has been detected by the Java Runtime Environ"
    long_b = "b" * 33
    entries = [
        (0, (tuple(frozenset([ord(c)]) for c in long_a),)),
        (1, (tuple(frozenset([ord("b")]) for _ in range(33)),)),
        (2, (tuple(frozenset([ord(c)]) for c in "xy"),)),
    ]
    bank = ShiftOrBank(entries)
    assert bank.has_chains and bank.n_words >= 3
    lines = [
        long_a,                       # exact
        "zz" + long_a + " tail",      # offset start (chain restarts)
        long_a[:-1],                  # one byte short: no match
        long_a[:30] + "X" + long_a[30:],  # broken at a word boundary
        long_b,                       # 33 b's
        "b" * 32,                     # one short
        "b" * 40,                     # long run: matches
        "xy " + "b" * 33,             # co-packed short + chain in one line
        "",
    ]
    enc = encode_lines(lines)
    got = np.asarray(bank._run(np.asarray(enc.u8.T), np.asarray(enc.lengths)))
    hosts = [re.compile(re.escape(long_a)), re.compile("b{33}"), re.compile("xy")]
    for i, host in enumerate(hosts):
        expect = [bool(host.search(ln)) for ln in lines]
        np.testing.assert_array_equal(
            got[: len(lines), i], expect, err_msg=f"col {i}"
        )


def test_mixed_literal_alternation_column_truncated_superset():
    """A primary-only column mixing a >31-position literal alternative
    with a \\d+ alternative rides bitglush TRUNCATED (the long
    alternative is cut so the bank stays chainless): the cube must be a
    SUPERSET of host re — exact on every short alternative, and exactly
    the 31-item prefix condition on the long one — and the column must
    be flagged in ``approx_cols`` so the engine re-verifies its events
    (tests/test_bitglush.py covers end-to-end exactness)."""
    from log_parser_tpu.patterns.bank import PatternBank
    from helpers import make_pattern, make_pattern_set

    rx = (
        "Connection is not available, request timed out after"
        "|HikariPool-\\d+ - Connection marked as broken"
        "|short one"
    )
    bank = PatternBank(
        [make_pattern_set([make_pattern("p0", regex=rx, confidence=0.5)])]
    )
    mb = MatcherBanks(bank, bitglush_max_words=192)
    assert mb.shiftor is None  # no exact-sequence columns in this bank
    col = next(i for i, c in enumerate(bank.columns) if c.regex == rx)
    assert mb.approx_cols == [col]
    assert mb.bitglush is not None and not mb.bitglush.has_chains
    lines = [
        "Connection is not available, request timed out after 30000ms",
        "HikariPool-1 - Connection marked as broken",
        "a short one here",
        "Connection is not available, request timed out",  # prefix only
        "HikariPool- - Connection marked as broken",  # \\d+ unmet
        "nothing",
    ]
    enc = encode_lines(lines)
    got = np.asarray(
        mb.cube(np.asarray(enc.u8.T), np.asarray(enc.lengths))
    )[: len(lines), col]
    host = compile_java_regex(rx)
    want = [bool(host.search(ln)) for ln in lines]
    # superset: every true match is flagged
    assert all(g or not w for g, w in zip(got, want))
    # exact everywhere except the long alternative's prefix-only line
    np.testing.assert_array_equal(
        got, [True, True, True, True, False, False]
    )


def test_adaptive_tier_split(monkeypatch):
    from log_parser_tpu.patterns.bank import PatternBank
    from helpers import make_pattern, make_pattern_set

    patterns = [
        make_pattern(f"p{i}", regex=f"literal-{i:03d}", confidence=0.5)
        for i in range(8)
    ]
    bank = PatternBank([make_pattern_set(patterns)])
    # under pinned Shift-Or and prefilter thresholds: nothing on the
    # Shift-Or tier; the columns ride the union multi-DFA (or the dense
    # bank without it)
    under = {"shiftor_min_columns": 10**9, "prefilter_min_columns": 10**9}
    small = MatcherBanks(
        bank, multi_min_columns=10**9, bitglush_max_words=0, **under
    )
    assert small.shiftor is None and len(small.dfa_cols) > 0
    multi = MatcherBanks(bank, bitglush_max_words=0, **under)
    assert multi.shiftor is None
    # every column the no-multi config kept dense rides the union instead
    assert sorted(multi.multi_cols + multi.dfa_cols) == sorted(small.dfa_cols)
    # the default plan: Shift-Or engages from one column
    wide = MatcherBanks(bank)
    assert wide.shiftor is not None
    assert len(wide.shiftor_cols) == 8  # all literal-shaped primaries


def test_word_budget_gate_reroutes_and_stays_exact():
    """A small shiftor_max_words reroutes DFA-backed literal columns off
    Shift-Or (no-DFA columns stay — it is their only device tier) and the
    rerouted bank produces an identical match cube."""
    import jax.numpy as jnp

    from helpers import make_pattern, make_pattern_set
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.patterns.bank import PatternBank

    patterns = [
        make_pattern(f"p{i}", regex=f"needle-{i:04d}", confidence=0.5)
        for i in range(80)  # ~80 x 11 bytes -> ~28 packed words
    ]
    bank = PatternBank([make_pattern_set(patterns)])

    wide = MatcherBanks(bank, shiftor_min_columns=1)
    assert wide.shiftor is not None and len(wide.shiftor_cols) == 80

    gated = MatcherBanks(bank, shiftor_min_columns=1, shiftor_max_words=4)
    assert gated.shiftor is None
    assert len(gated.multi_cols) + len(gated.prefilter_cols) + len(
        gated.dfa_cols
    ) + len(gated.bitglush_cols) >= 80  # every literal column found another tier

    lines = [f"x needle-{i:04d} y" for i in range(0, 80, 7)] + ["no match here"]
    enc = encode_lines(lines)
    lt = jnp.asarray(enc.u8.T)
    ln = jnp.asarray(enc.lengths)
    np.testing.assert_array_equal(
        np.asarray(wide.cube(lt, ln))[: len(lines)],
        np.asarray(gated.cube(lt, ln))[: len(lines)],
    )
