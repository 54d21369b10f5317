"""Bit-parallel extended Shift-And engine: compile coverage, exactness vs
host ``re`` per feature, fuzz over random lines, and the tier wiring."""

from __future__ import annotations

import random
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from log_parser_tpu.golden.javacompat import compile_java_regex
from log_parser_tpu.ops.bitglush import BitGlushBank
from log_parser_tpu.ops.encode import encode_lines
from log_parser_tpu.ops.match import pack_byte_pairs
from log_parser_tpu.patterns.regex.bitprog import (
    BitUnsupportedError,
    compile_bitprog_regex,
    expand_asserts,
    has_asserts,
)


def run_bank(
    regexes: list[tuple[str, bool]], lines: list[str], deassert: bool = False
) -> np.ndarray:
    entries = [
        (i, compile_bitprog_regex(rx, ci)) for i, (rx, ci) in enumerate(regexes)
    ]
    if deassert:
        entries = [(i, expand_asserts(p)) for i, p in entries]
        assert not any(has_asserts(p) for _, p in entries)
    bank = BitGlushBank(entries)
    enc = encode_lines(lines)
    lines_tb = jnp.asarray(enc.u8.T)
    lens = jnp.asarray(enc.lengths)
    B = enc.u8.shape[0]
    init, step, finish = bank.pair_stepper(B, lens)
    pairs, ts = pack_byte_pairs(lines_tb)
    carry, _ = jax.lax.scan(
        lambda c, xs: (step(c, xs[0][0], xs[0][1], xs[1]), None),
        init,
        (pairs, ts),
    )
    return np.asarray(finish(carry))[: len(lines)]


def check_exact(
    regexes: list[tuple[str, bool]], lines: list[str], deassert: bool = False
):
    got = run_bank(regexes, lines, deassert=deassert)
    for j, (rx, ci) in enumerate(regexes):
        host = compile_java_regex(rx, ci)
        for i, line in enumerate(lines):
            want = host.search(line) is not None
            assert got[i, j] == want, (
                f"regex {rx!r} ci={ci} line {line!r}: got {got[i, j]}, want {want}"
            )


FEATURES = [
    # plain literals, incl. one spanning >32 positions (cross-word shift)
    ("OutOfMemoryError", False),
    ("A fatal error has been detected by the Java Runtime Environment", False),
    # classes and bounded repeats
    ("x[45]\\d\\d", False),
    ("a{3}b", False),
    ("ab{2,4}c", False),
    # plus / star / optional
    ("Port \\d+ in use", False),
    ("Exit Code:\\s*137", False),
    ("colou?r", False),
    # gaps
    ("status.*red", False),
    ("node .* not ready", False),
    # alternation incl. nested group expansion
    ("foo|ba[rz]", False),
    ("liquibase.* (failed|error)", False),
    ("(sorry, )?too many (connections|clients)", False),
    # anchors and boundaries
    ("^startline", False),
    ("endline$", False),
    ("^whole line$", False),
    ("\\btimeout\\b", False),
    ("\\bdial tcp\\b", False),
    ("\\b(WARN|WARNING)\\b", True),
    ("\\b\\w*Exception\\b|\\b\\w*Error\\b", False),
    ("^\\s*at\\s+[\\w\\.\\$]+\\(.*\\)\\s*$", False),
    # case-insensitive
    ("deadlock", True),
    # non-word boundary
    ("\\Bood", False),
]

FEATURE_LINES = [
    "",
    "x",
    "java.lang.OutOfMemoryError: heap",
    "A fatal error has been detected by the Java Runtime Environment:",
    "the Java Runtime Environment",
    "x503 status",
    "x403",
    "x903",
    "aaab",
    "aab",
    "abbc abbbbc",
    "abc",
    "Port 8080 in use",
    "Port  in use",
    "Exit Code:137",
    "Exit Code: 137",
    "Exit Code :137",
    "color colour colouur",
    "status is red",
    "statusred",
    "red status",
    "node web-1 not ready",
    "foo bar baz",
    "liquibase migration error",
    "liquibase ok",
    "too many connections",
    "sorry, too many clients",
    "sorry too many clients",
    "startline here",
    "not startline",
    "an endline",
    "endline not",
    "whole line",
    " whole line",
    "timeout after",
    "timeouts after",
    "xtimeout",
    "dial tcp 10.0.0.7",
    "dials tcp",
    "warn: warning things",
    "WARNED",
    "threw FooException here",
    "Exceptional",
    "plain Error",
    "  at com.example.Service.handle(Service.java:42)",
    "at com.example.run(X.java:1) extra",
    "  at  spaced(Y.scala:2)  ",
    "DEADLOCK found",
    "good wood",
    "oodles",
    "ood start",
]


def test_feature_exactness():
    check_exact(FEATURES, FEATURE_LINES)


def test_feature_exactness_deasserted():
    """The de-assert rewrite (expand_asserts) stays exact on every
    feature, including leading/trailing \\b, \\B, and their ^/$/case
    interactions."""
    check_exact(FEATURES, FEATURE_LINES, deassert=True)


def test_deassert_shapes():
    """Shapes at the edges of the rewrite: single-item \\b\\w+\\b (PLUS
    split both ends), pre-assert on a PLUS, impure trailing byteset
    (split), cascade trailing (uniform), and \\B both ways."""
    regexes = [
        ("\\b\\w+\\b", False),
        ("\\bx+y\\b", False),
        ("x[=a]\\b", False),  # impure final byteset: split
        # cascade [\s*, b] mixes word-ness across accepting positions ->
        # rejected ("word-ness-impure trailing cascade"); asserted below
        ("ab\\s*\\b", False),
        ("\\Bood\\b", False),
        ("\\btag\\B", False),
    ]
    lines = [
        "", "x", "word", " word ", "=word=", "xxy", "xy z", "axy.",
        "x= y", "xa b", "ab  c", "ab", "abc", "good food", "oodles",
        "tag", "tags", "tag s", "a tag", "atag b", "x=", "x=,", "=x",
    ]
    with pytest.raises(BitUnsupportedError):
        expand_asserts(compile_bitprog_regex("ab\\s*\\b", False))
    for rx, ci in regexes:
        try:
            prog = expand_asserts(compile_bitprog_regex(rx, ci))
        except BitUnsupportedError:
            continue  # rejected shapes stay on gated tiers — fine
        assert not has_asserts(prog)
        check_exact([(rx, ci)], lines, deassert=True)


def test_generative_fuzz_deasserted():
    """Random regexes over the assert-bearing fragment, run through
    expand_asserts, must match host re exactly."""
    rng = random.Random(424242)
    regexes: list[tuple[str, bool]] = []
    attempts = 0
    while len(regexes) < 60 and attempts < 1500:
        attempts += 1
        rx = _gen_regex(rng)
        if "\\b" not in rx and rng.random() < 0.8:
            continue  # bias toward assert-bearing shapes
        ci = rng.random() < 0.2
        try:
            prog = expand_asserts(compile_bitprog_regex(rx, ci))
        except BitUnsupportedError:
            continue
        assert not has_asserts(prog)
        try:
            compile_java_regex(rx, ci)
        except Exception:
            continue
        regexes.append((rx, ci))
    assert len(regexes) >= 40, f"generator too restrictive: {len(regexes)}"
    alphabet = "abcxyz05 _-:AB9\t."
    lines = [
        "".join(rng.choice(alphabet) for _ in range(rng.randrange(0, 50)))
        for _ in range(250)
    ]
    lines += ["", "a", " ", "foo", "bar:", "x0 x0 x0", "abc05xyz", "a" * 120]
    check_exact(regexes, lines, deassert=True)


def test_builtin_bank_fully_deasserted():
    """The builtin library's bit bank must come out of the de-assert
    rewrite with every word-ness op group off (that is the point: ~8 of
    ~18 ops leave the scan body)."""
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.patterns.bank import PatternBank
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets

    bank = PatternBank(load_builtin_pattern_sets())
    mb = MatcherBanks(bank)
    g = mb.bitglush
    assert g is not None
    assert not g.has_preassert and not g.has_tb and not g.needs_wordness


def test_builtin_union_columns_exact_on_corpus():
    """Every builtin dense-eligible regex that compiles to a bit program
    matches the host `re` exactly over a mixed corpus."""
    from log_parser_tpu.patterns.bank import PatternBank
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets

    bank = PatternBank(load_builtin_pattern_sets())
    regexes = []
    for col in bank.columns:
        if col.dfa is None or col.exact_seqs is not None:
            continue
        try:
            compile_bitprog_regex(col.regex, col.case_insensitive)
        except BitUnsupportedError:
            continue
        regexes.append((col.regex, col.case_insensitive))
    # MAX_EXACT_LEN=64 routes long literal alternations to Shift-Or
    # chains, so ~32 dense-eligible columns remain for the bit tier
    assert len(regexes) >= 25

    rng = random.Random(7)
    words = [
        "ERROR", "error", "timeout", "dial", "tcp", "OOMKilled", "status",
        "red", "node", "not", "ready", "at", "failed", "Migration", "x",
        "Exception", "Error", "deadlock", "FATAL:", "too", "many",
        "connections", "goroutine", "137", "Exit", "Code:", "segfault",
        "0af3", "(", ")", "running", "[running]", "upstream", "Full", "GC",
    ]
    lines = [
        " ".join(rng.choice(words) for _ in range(rng.randrange(0, 12)))
        for _ in range(300)
    ]
    lines += [
        "java.lang.OutOfMemoryError: Java heap space",
        "  at com.example.Service.handle(Service.java:42)",
        "goroutine 42 [running]",
        "FATAL:  too many connections",
        "Exit Code:  137",
        "segfault at deadbeef",
        "upstream connect error or disconnect",
        "node web-1 not ready",
        "liquibase update failed",
    ]
    check_exact(regexes, lines)


def test_fuzz_random_ascii():
    regexes = FEATURES
    rng = random.Random(1234)
    alphabet = (
        "abcdefgxyz XYZ0123459_().:-\t"
        "ABCDE"
    )
    lines = [
        "".join(rng.choice(alphabet) for _ in range(rng.randrange(0, 70)))
        for _ in range(400)
    ]
    check_exact(regexes, lines)


def test_sink_mode_full_width_lines():
    """Sink-mode acceptance at the scan's last byte: a line that fills
    every scanned byte (length == T, no padding inside the scan) relies
    on finish()'s virtual padding step to sweep last-byte finals into
    their sinks — both the plain and the ``$`` kind."""
    regexes = [
        ("Error$", False),
        ("Error", False),
        ("fail(ed)?$", False),
        ("x[45]\\d$", False),
    ]
    # encode_lines pads T to a multiple of 32: 32-char lines are
    # full-width rows, shorter ones see real in-scan padding
    lines = [
        "x" * 27 + "Error",          # 32 chars, Error at the very end
        "Error" + "y" * 27,          # Error mid-line, 32 chars
        "z" * 25 + "failed",         # 31 chars: one padding byte in scan
        "q" * 26 + "failed",         # 32 chars, ends at T
        "w" * 28 + "fail",           # optional group empty at line end
        "v" * 29 + "x45",            # $ after class item, full width
        "v" * 20 + "x45" + "z" * 9,  # same match mid-line: $ must miss
        "",
        "Error",
    ]
    entries = [
        (i, compile_bitprog_regex(rx, ci)) for i, (rx, ci) in enumerate(regexes)
    ]
    bank = BitGlushBank(entries)
    assert bank.use_sinks
    check_exact(regexes, lines)


def test_sink_mode_skippable_cascade_into_sink():
    """Finals that cascade back through a trailing skippable suffix all
    reach the sink via the existing closure unrolling."""
    regexes = [("ab?c?", False), ("de*", False), ("fg?$", False)]
    lines = ["za", "zab", "zabc", "zd", "zdee", "zf", "zfg", "zfgh", "q"]
    entries = [
        (i, compile_bitprog_regex(rx, ci)) for i, (rx, ci) in enumerate(regexes)
    ]
    assert BitGlushBank(entries).use_sinks
    check_exact(regexes, lines)


def test_trailing_boundary_bank_keeps_hits_path():
    """A trailing \\b final is sink-ineligible: the bank keeps the
    per-byte hit path and stays exact."""
    regexes = [("Error\\b", False), ("plain", False)]
    entries = [
        (i, compile_bitprog_regex(rx, ci)) for i, (rx, ci) in enumerate(regexes)
    ]
    bank = BitGlushBank(entries)
    assert not bank.use_sinks
    check_exact(regexes, ["Error", "Errors", "xError", "plainly", "no"])


def test_unsupported_shapes_rejected():
    for rx in [
        "(ab)+c",  # unbounded group repeat
        "a{40}",  # oversized bound
        "\\bx?y",  # assertion before optional item
        "^$",  # assertion-only
        "(a|b)(c|d)(e|f)(g|h)(i|j)(k|l)(m|n)",  # 128 alts > 64 cap
        "abc^",  # trailing anchor (legal regex, never matches)
        "x*^ab",  # mid-pattern anchor, satisfiable via empty prefix
    ]:
        with pytest.raises(BitUnsupportedError):
            compile_bitprog_regex(rx, False)


def test_boundary_rewrite_requires_consuming_next_item():
    """'\\b\\w*x?-' must NOT take the \\b\\w* drop rewrite: x? can match
    empty, leaving the non-word '-' as the first consumed byte, so the
    boundary requirement survives. The shape is rejected (it routes to
    the union tier) instead of compiling to a false-positive program;
    the consuming-next variant still compiles and stays exact."""
    with pytest.raises(BitUnsupportedError):
        compile_bitprog_regex("\\b\\w*x?-", False)
    check_exact(
        [("\\b\\w*x-", False)],
        ["a -", "a x-", "ax-", "a-", " -", "x-", "-", "yx-", " yx-"],
    )


def test_boundary_rewrite_leading_unanchored_only():
    """The \\b\\w* drop rewrite is sound only when \\b\\w* is the leading
    consuming element of an unanchored alternative — a preceding consumed
    item or a ^ pins the left edge the containment argument needs free.
    The advisor's counterexamples: '=\\b\\w*Exception' would miss
    '=FooException', 'a\\b\\w*Exception' would falsely match 'aException',
    '^\\b\\w*Exception' would miss 'FooException'. All three must be
    rejected (routing the column to an exact automaton tier); the leading
    unanchored shape still compiles and stays exact."""
    for rx in ["=\\b\\w*Exception", "a\\b\\w*Exception", "^\\b\\w*Exception"]:
        with pytest.raises(BitUnsupportedError):
            compile_bitprog_regex(rx, False)
    check_exact(
        [("\\b\\w*Exception\\b", False)],
        ["=FooException", "aException", "FooException", "threw FooException x"],
    )


def _gen_regex(rng: random.Random) -> str:
    """Random regex over (a superset of) the bit-parallel fragment."""
    def atom() -> str:
        r = rng.random()
        if r < 0.35:
            return rng.choice("abcxyz05 _-:")  # literal (incl. specials-free)
        if r < 0.5:
            return rng.choice(["[abc]", "[0-9]", "[a-cx-z]", "[^a-y]"])
        if r < 0.65:
            return rng.choice(["\\d", "\\w", "\\s", "."])
        return rng.choice(["foo", "bar:", "x0 "])  # short literal run

    def item() -> str:
        a = atom()
        r = rng.random()
        if r < 0.55:
            return a
        if r < 0.7:
            return a + "+"
        if r < 0.8:
            return a + "*"
        if r < 0.9:
            return a + "?"
        lo = rng.randrange(0, 3)
        return a + "{%d,%d}" % (lo, lo + rng.randrange(0, 3))

    def branch() -> str:
        n = rng.randrange(1, 7)
        s = "".join(item() for _ in range(n))
        if rng.random() < 0.15:
            s = "\\b" + s
        if rng.random() < 0.1:
            s = "^" + s
        if rng.random() < 0.15:
            s = s + "\\b"
        if rng.random() < 0.1:
            s = s + "$"
        return s

    return "|".join(branch() for _ in range(rng.randrange(1, 4)))


def test_generative_fuzz_vs_host_re():
    """Generate random regexes across the whole supported fragment, keep
    those the compiler accepts, and check device-vs-host exactness over
    random and adversarial lines — one shared bank so the scan compiles
    once."""
    rng = random.Random(20260730)
    regexes: list[tuple[str, bool]] = []
    attempts = 0
    while len(regexes) < 120 and attempts < 1200:
        attempts += 1
        rx = _gen_regex(rng)
        ci = rng.random() < 0.2
        try:
            compile_bitprog_regex(rx, ci)
        except BitUnsupportedError:
            continue
        try:  # the golden compiler must accept it too
            compile_java_regex(rx, ci)
        except Exception:
            continue
        regexes.append((rx, ci))
    assert len(regexes) >= 80, f"generator too restrictive: {len(regexes)}"

    alphabet = "abcxyz05 _-:AB9\t."
    lines = [
        "".join(rng.choice(alphabet) for _ in range(rng.randrange(0, 50)))
        for _ in range(250)
    ]
    lines += ["", "a", " ", "foo", "bar:", "x0 x0 x0", "foofoofoo",
              "abc05xyz", ":::", "a" * 120]
    check_exact(regexes, lines)


def test_matcher_banks_bit_tier_cube_parity():
    """MatcherBanks with the bit tier forced on (it is TPU-only by
    default) produces the identical cube to the default CPU tiering over
    the builtin library."""
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.patterns.bank import PatternBank
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets

    bank = PatternBank(load_builtin_pattern_sets())
    bit = MatcherBanks(bank)
    base = MatcherBanks(bank, bitglush_max_words=0)
    # long literal alternations ride Shift-Or chains (MAX_EXACT_LEN=64);
    # the bit tier keeps the ~32 genuinely non-literal columns
    assert len(bit.bitglush_cols) >= 25
    assert not base.bitglush_cols

    lines = [
        "java.lang.OutOfMemoryError: Java heap space",
        "[Full GC (Ergonomics) 255M->250M(256M), 0.41 secs]",
        "dial tcp 10.0.0.7:5432: Connection refused",
        "  at com.example.Service.handle(Service.java:42)",
        "ERROR request failed with IllegalStateException",
        "goroutine 42 [running]",
        "FATAL:  too many connections",
        "liquibase update failed",
        "node web-1 not ready",
        "2026-07-29T07:00:00Z INFO reconcile tick 1 status=ok",
        "",
    ]
    enc = encode_lines(lines)
    lt = jnp.asarray(enc.u8.T)
    ln = jnp.asarray(enc.lengths)
    np.testing.assert_array_equal(
        np.asarray(bit.cube(lt, ln))[: len(lines)],
        np.asarray(base.cube(lt, ln))[: len(lines)],
    )


def test_pallas_kernel_parity_interpret():
    """The Pallas kernel (interpreter mode — no TPU needed) produces the
    identical hit words / columns as the scan-path stepper. Small bank +
    short lines keep the interpreted loop fast."""
    from log_parser_tpu.ops.bitglush_pallas import bitglush_hits_pallas

    regexes = [
        ("OutOfMemoryError", False),
        ("Exit Code:\\s*137", False),
        ("status.*red", False),
        ("\\btimeout\\b", True),
        ("^\\s*at .*\\)$", False),
        ("colou?r|Port \\d+", False),
    ]
    entries = [
        (i, compile_bitprog_regex(rx, ci)) for i, (rx, ci) in enumerate(regexes)
    ]
    bank = BitGlushBank(entries)
    lines = [
        "java OutOfMemoryError x",
        "Exit Code: 137",
        "status went red",
        "TIMEOUT after",
        "xtimeout",
        "  at com.x(Y.java:1)",
        "color Port 80",
        "",
    ]
    enc = encode_lines(lines)
    hits = bitglush_hits_pallas(
        bank, jnp.asarray(enc.u8.T), jnp.asarray(enc.lengths), interpret=True
    )
    got = np.asarray(bank.columns_from_hits(hits))[: len(lines)]
    want = run_bank(regexes, lines)
    np.testing.assert_array_equal(got, want)


def test_pallas_engine_integration(monkeypatch):
    """LOG_PARSER_TPU_PALLAS=1 routes the bit tier through the kernel in
    MatcherBanks.cube (interpreter mode off-TPU) — including when the bit
    tier is the only populated tier — and the cube matches the default
    path."""
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.patterns.bank import PatternBank
    from log_parser_tpu.patterns.builtin import load_builtin_pattern_sets

    monkeypatch.setenv("LOG_PARSER_TPU_PALLAS", "1")
    bank = PatternBank(load_builtin_pattern_sets())
    pal = MatcherBanks(bank)
    assert pal.bitglush_use_pallas and pal.bitglush_cols
    monkeypatch.delenv("LOG_PARSER_TPU_PALLAS")
    base = MatcherBanks(bank)
    assert not base.bitglush_use_pallas

    lines = [
        "java.lang.OutOfMemoryError: Java heap space",
        "goroutine 42 [running]",
        "  at com.example.Service.handle(Service.java:42)",
        "plain INFO line",
        "",
    ]
    enc = encode_lines(lines)
    lt, ln = jnp.asarray(enc.u8.T), jnp.asarray(enc.lengths)
    np.testing.assert_array_equal(
        np.asarray(pal.cube(lt, ln))[: len(lines)],
        np.asarray(base.cube(lt, ln))[: len(lines)],
    )


def test_word_count():
    progs = [
        compile_bitprog_regex(rx, ci) for rx, ci in FEATURES
    ]
    assert BitGlushBank.count_packed_words(progs) == BitGlushBank(
        list(enumerate(progs))
    ).n_words


# ---------------------------------------------------- truncation + verify


def test_first_fit_packing_never_straddles():
    """The packing invariant the chainless shift relies on: every ≤32-bit
    allocation is placed INSIDE one word (start%32 + alloc ≤ 32), and
    >32-bit allocations start word-aligned."""
    progs = [compile_bitprog_regex(rx, ci) for rx, ci in FEATURES]
    allocs = BitGlushBank._alt_allocs(progs)
    starts, n_words = BitGlushBank._plan(allocs)
    assert len(starts) == len(allocs)
    for s, a in zip(starts, allocs):
        if a <= 32:
            assert s % 32 + a <= 32, (s, a)
        else:
            assert s % 32 == 0, (s, a)
        assert s + a <= n_words * 32


def test_chained_bank_exact_vs_host_re():
    """A bank holding a >32-position alternative (word-straddling
    allocation → has_chains → conditional carry) must stay exact —
    including co-packed short, caret, and skip programs sharing the
    bank, and matches crossing both word boundaries of the chain."""
    long_rx = "could not connect to server: Connection refused no retry"
    regexes = [
        (long_rx, False),
        ("^anchored", False),
        ("time.?out", False),
        ("x\\d+y", False),
    ]
    progs = [compile_bitprog_regex(rx, ci) for rx, ci in regexes]
    bank = BitGlushBank(list(enumerate(progs)))
    assert bank.has_chains and bank.n_words >= 3
    rng = random.Random(7)
    alphabet = "cold nt sever:Cfu Retry anhItime-outx123y "
    lines = [
        "".join(rng.choice(alphabet) for _ in range(rng.randrange(0, 70)))
        for _ in range(200)
    ] + [
        long_rx,                       # exact
        "zz " + long_rx + " tail",     # offset: chain restart mid-line
        long_rx[:-1],                  # one short: no match
        long_rx[:33] + "X" + long_rx[34:],  # broken at the word boundary
        "anchored here",               # caret at start
        "not anchored here",           # caret unmet
        "a timeout b",
        "x42y",
        "",
    ]
    check_exact(regexes, lines)


def test_bitglush_budget_holds_after_truncation():
    """The constructed bank must NEVER exceed bitglush_max_words, however
    post-admission truncation reshapes the packing (r5 code review).
    Two ways the admission-time price can go stale: dropping \\b/\\B
    post-asserts can flip the bank sink-eligible (+1 bit per alternative
    bank-wide — in engine banks the never-truncated context columns keep
    their own \\b finals, so the flip needs every remaining b-final to
    sit on truncated alternatives), and first-fit packing is non-monotone
    (a SHRUNK allocation can reshuffle the plan into more words).  The
    post-truncation re-price/shed loop in MatcherBanks is the invariant's
    single enforcement point; this pins it across budgets on a bank mixing
    exactly-one-word sequence columns with a truncated long primary."""
    from helpers import make_pattern, make_pattern_set
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.runtime import AnalysisEngine

    # six distinct 32-position non-literal SEQUENCE-EVENT regexes:
    # non-truncatable (no cheap repair for the temporal chain), exactly
    # one word each while the bank is sink-less, two words once sinks
    # flip on (32 + 1 sink = 33 bits straddles a word boundary)
    seq_rx = [f"stage {k} failed with retcode n" + "\\d\\d\\d" for k in range(6)]
    assert all(
        len(s) - 3 * len("\\d") + 3 == 32 for s in seq_rx
    )  # 29 literal chars + 3 class positions
    long_b = "Connection is not available, request timed out after\\b"
    sets = [
        make_pattern_set(
            [
                make_pattern(
                    "plong",
                    regex=long_b,
                    confidence=0.9,
                    sequences=[(1.5, seq_rx)],
                )
            ]
        )
    ]
    engine = AnalysisEngine(sets, ScoringConfig())
    for budget in (2, 4, 8, 12):
        mb = MatcherBanks(
            engine.bank,
            bitglush_max_words=budget,
            shiftor_min_columns=10**9,
            prefilter_min_columns=10**9,
            multi_min_columns=10**9,
        )
        if mb.bitglush is not None:
            assert mb.bitglush.n_words <= budget, (budget, mb.bitglush.n_words)


def test_approx_caches_invalidate_on_matcher_swap():
    """ADVICE r4: the lazily-built approx caches are keyed on matcher
    object identity — rebuilding/replacing ``engine._matchers`` after a
    first analyze must refresh ``_approx_patterns``/``_approx_secondaries``,
    or stale (empty) repair sets would skip the host re-verification of
    truncated columns."""
    from helpers import make_pattern, make_pattern_set
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.runtime import AnalysisEngine

    long_lit = "Connection is not available, request timed out after"
    sets = [
        make_pattern_set(
            [
                make_pattern("plong", regex=long_lit, confidence=0.9),
                make_pattern("pshort", regex="timed out", confidence=0.5),
            ]
        )
    ]
    engine = AnalysisEngine(sets, ScoringConfig())
    # start from matchers with the bit tier off: nothing truncated,
    # caches built empty
    engine._matchers = MatcherBanks(engine.bank, bitglush_max_words=0)
    assert not engine.matchers.approx_cols
    assert not engine._approx_patterns().any()
    assert engine._approx_secondaries() == []
    # swap in the default plan, whose bit tier truncates the long literal
    engine._matchers = MatcherBanks(engine.bank)
    assert engine.matchers.approx_cols
    # the caches must follow the swap, not serve the stale empty sets
    assert engine._approx_patterns().any()


def test_truncated_primary_column_engine_exact():
    """End-to-end: a primary-only column whose long alternative is
    truncated on device must still produce EXACTLY the reference's
    events — the engine re-verifies flagged lines with the host regex
    and drops prefix-only false positives before scoring, frequency
    recording, and assembly."""
    from helpers import make_pattern, make_pattern_set
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.golden.engine import GoldenAnalyzer
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.patterns.bank import PatternBank
    from log_parser_tpu.runtime import AnalysisEngine

    long_lit = "Connection is not available, request timed out after"
    sets = [
        make_pattern_set(
            [
                make_pattern("plong", regex=long_lit, confidence=0.9),
                make_pattern("pshort", regex="timed out", confidence=0.5),
            ]
        )
    ]
    logs = "\n".join(
        [
            f"{long_lit} 30000ms",        # true match (both patterns)
            "Connection is not available, request timed out",  # prefix only
            "request timed out again",     # short pattern only
            "clean line",
        ]
    )
    data = PodFailureData(logs=logs)

    engine = AnalysisEngine(sets, ScoringConfig())
    # the other tiers pinned off, so the long alternative rides the
    # (truncated) bit tier
    engine._matchers = MatcherBanks(
        engine.bank,
        bitglush_max_words=192,
        shiftor_min_columns=10**9,
        prefilter_min_columns=10**9,
        multi_min_columns=10**9,
    )
    mb = engine.matchers
    long_col = next(
        i for i, c in enumerate(engine.bank.columns) if c.regex == long_lit
    )
    assert long_col in mb.approx_cols

    got = engine.analyze(data)
    want = GoldenAnalyzer(sets, ScoringConfig()).analyze(data)
    assert [e.line_number for e in got.events] == [
        e.line_number for e in want.events
    ]
    assert [e.matched_pattern.id for e in got.events] == [
        e.matched_pattern.id for e in want.events
    ]
    for g, w in zip(got.events, want.events):
        assert abs(g.score - w.score) < 1e-9
    # the false positive line (prefix only) produced no plong event
    assert all(
        not (e.matched_pattern.id == "plong" and e.line_number == 2)
        for e in got.events
    )


def test_truncation_roles():
    """Secondary-role long columns truncate (their distances get the
    exact host repair in the engine); sequence-event-role long columns
    never truncate — they ride Shift-Or's chain path and stay exact in
    the cube."""
    from helpers import make_pattern, make_pattern_set
    from log_parser_tpu.models.pattern import (
        SecondaryPattern,
        SequenceEvent,
        SequencePattern,
    )
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.patterns.bank import PatternBank

    sec_lit = "Back-off restarting failed container"
    seq_lit = "Liveness probe failed repeatedly for main container"
    p1 = make_pattern("p1", regex="primary thing", confidence=0.5)
    p1.secondary_patterns = [
        SecondaryPattern(regex=sec_lit, weight=0.5, proximity_window=5)
    ]
    p1.sequence_patterns = [
        SequencePattern(
            description="d",
            bonus_multiplier=0.4,
            events=[SequenceEvent(regex=seq_lit)],
        )
    ]
    p2 = make_pattern("p2", regex=sec_lit, confidence=0.5)
    bank = PatternBank([make_pattern_set([p1, p2])])
    mb = MatcherBanks(bank)
    sec_col = next(i for i, c in enumerate(bank.columns) if c.regex == sec_lit)
    seq_col = next(i for i, c in enumerate(bank.columns) if c.regex == seq_lit)
    # secondary-role long column: truncated on device, flagged approx
    assert sec_col in mb.approx_cols
    assert sec_col not in mb.shiftor_cols
    # sequence-event-role long column: exact, on the Shift-Or chain path
    assert seq_col not in mb.approx_cols
    assert seq_col in mb.shiftor_cols
    assert mb.shiftor.has_chains
    lines = [seq_lit, seq_lit[:-1], "x " + seq_lit + " y", ""]
    enc = encode_lines(lines)
    got = np.asarray(
        mb.cube(jnp.asarray(enc.u8.T), jnp.asarray(enc.lengths))
    )[: len(lines), seq_col]
    np.testing.assert_array_equal(got, [True, False, True, False])


def test_truncated_secondary_distance_repair():
    """End-to-end: a pattern whose long SECONDARY is truncated on device
    must still score exactly — the engine verifies the claimed nearest
    lines and, when both were prefix-only false positives, recovers the
    true distance (or its absence) by the bounded host scan."""
    from helpers import make_pattern, make_pattern_set
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.golden.engine import GoldenAnalyzer
    from log_parser_tpu.models.pattern import SecondaryPattern
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.runtime import AnalysisEngine

    sec_lit = "Back-off restarting failed container"
    prefix_only = sec_lit[:31]  # matches the truncated program only
    p = make_pattern("pp", regex="primary thing", confidence=0.8)
    p.secondary_patterns = [
        SecondaryPattern(regex=sec_lit, weight=0.5, proximity_window=8)
    ]
    sets = [make_pattern_set([p])]

    def build_engine():
        e = AnalysisEngine(sets, ScoringConfig())
        e._matchers = MatcherBanks(
            e.bank,
            bitglush_max_words=192,
            shiftor_min_columns=10**9,
            prefilter_min_columns=10**9,
            multi_min_columns=10**9,
        )
        sec_col = next(
            i for i, c in enumerate(e.bank.columns) if c.regex == sec_lit
        )
        assert sec_col in e.matchers.approx_cols
        return e

    cases = [
        # (log lines, label)
        (
            [
                "primary thing here",
                prefix_only,          # false positive at distance 1
                "filler",
                sec_lit,              # true hit at distance 3
            ],
            "false-then-true",
        ),
        (
            ["x", "primary thing here", sec_lit + " tail"],
            "true-adjacent",
        ),
        (
            ["primary thing here", prefix_only, "y"],
            "false-only",
        ),
        (
            [prefix_only, "a", "primary thing here", "b", prefix_only],
            "false-both-sides",
        ),
    ]
    for lines, label in cases:
        data = PodFailureData(logs="\n".join(lines))
        got = build_engine().analyze(data)
        want = GoldenAnalyzer(sets, ScoringConfig()).analyze(data)
        assert len(got.events) == len(want.events), label
        for a, b in zip(got.events, want.events):
            assert a.line_number == b.line_number, label
            assert abs(a.score - b.score) < 1e-9, (
                label,
                a.score,
                b.score,
            )


def test_truncated_caret_alternative_stays_chainless():
    """Regression (r4 review): the truncation budget must reserve the
    caret guard bit — a ^-anchored >31-position primary-only column
    must truncate to an allocation that fits one word, keeping the
    bank chainless, and stay exact end-to-end via host re-verify."""
    from helpers import make_pattern, make_pattern_set
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.golden.engine import GoldenAnalyzer
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.runtime import AnalysisEngine

    long_anchored = "^FATAL: unrecoverable disk failure on device"  # 43 items
    sets = [
        make_pattern_set(
            [make_pattern("pa", regex=long_anchored, confidence=0.9)]
        )
    ]
    engine = AnalysisEngine(sets, ScoringConfig())
    engine._matchers = MatcherBanks(
        engine.bank,
        bitglush_max_words=192,
        shiftor_min_columns=10**9,
        prefilter_min_columns=10**9,
        multi_min_columns=10**9,
    )
    mb = engine.matchers
    assert mb.bitglush is not None
    assert not mb.bitglush.has_chains  # the budget reserved the guard bit
    assert mb.approx_cols  # truncated -> engine verifies

    body = "FATAL: unrecoverable disk failure on device sda"
    logs = "\n".join(
        [
            body,                        # anchored true match
            "x " + body,                 # caret unmet
            body[:40],                   # prefix of the TRUNCATED region only
            "clean",
        ]
    )
    data = PodFailureData(logs=logs)
    got = engine.analyze(data)
    want = GoldenAnalyzer(sets, ScoringConfig()).analyze(data)
    assert [(e.line_number, e.matched_pattern.id) for e in got.events] == [
        (e.line_number, e.matched_pattern.id) for e in want.events
    ]
    for a, b in zip(got.events, want.events):
        assert abs(a.score - b.score) < 1e-9


def test_all_poison_corpus_zero_events():
    """Worst case for truncation: EVERY line is the 31-char prefix of a
    long primary literal. The device flags every line (K ladder may
    climb), the engine's host verify drops every record, and the result
    is exactly golden's: zero events, NONE summary, zero frequency."""
    from helpers import make_pattern, make_pattern_set
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.golden.engine import GoldenAnalyzer
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.runtime import AnalysisEngine

    lit = "Connection is not available, request timed out after"
    sets = [make_pattern_set([make_pattern("pl", regex=lit, confidence=0.9)])]
    engine = AnalysisEngine(sets, ScoringConfig())
    engine._matchers = MatcherBanks(
        engine.bank,
        bitglush_max_words=MatcherBanks.BITGLUSH_MAX_WORDS,
        shiftor_min_columns=10**9,
        prefilter_min_columns=10**9,
        multi_min_columns=10**9,
    )
    assert engine.matchers.approx_cols
    logs = "\n".join([lit[:31]] * 5000)
    data = PodFailureData(logs=logs)
    golden = GoldenAnalyzer(sets, ScoringConfig())
    got = engine.analyze(data)
    want = golden.analyze(data)
    assert got.events == [] and want.events == []
    assert got.summary.to_dict() == want.summary.to_dict()
    assert (
        engine.frequency.get_frequency_statistics()
        == golden.frequency.get_frequency_statistics()
    )
