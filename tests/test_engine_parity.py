"""TPU engine vs golden reference: full-pipeline score parity.

The contract under test: for any pattern library and any log,
``AnalysisEngine.analyze`` must produce the same events in the same
discovery order with scores within 1e-9 of ``GoldenAnalyzer.analyze``
(budget is 1e-6; f64 kernels land ~1e-13), including cross-request
frequency-state evolution."""

import random

import numpy as np
import pytest

from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.golden import GoldenAnalyzer
from log_parser_tpu.models import PodFailureData
from log_parser_tpu.runtime import AnalysisEngine
from tests.conftest import FakeClock
from tests.helpers import make_pattern, make_pattern_set

TOL = 1e-9

FRAGMENTS = [
    "OutOfMemoryError",
    "Connection refused",
    "GC overhead",
    "dial tcp",
    "segfault",
    "probe failed",
    "disk pressure",
    "CrashLoop",
    "exit code 137",
    "permission denied",
]

NOISE = [
    "INFO all systems nominal",
    "metric cpu=0.3 mem=0.7",
    "GET /healthz 200",
    "reconciling deployment web",
    "",  # interior empty line
    "ERROR upstream timeout",  # context: error
    "WARN retry scheduled",  # context: warn
    "    at com.example.Foo.bar(Foo.java:42)",  # context: stack
    "caught IllegalStateException",  # context: exception
    "naïve UTF-8 line é",  # non-ASCII -> host verify path
    "progress 42%\rdone",  # lone \r inside a line
]


def random_library(rng: random.Random, n_patterns: int):
    severities = ["CRITICAL", "HIGH", "MEDIUM", "LOW", "INFO", "Bogus", ""]
    patterns = []
    for i in range(n_patterns):
        frag = rng.choice(FRAGMENTS)
        regex = rng.choice(
            [
                frag,
                rf"\b{frag.split()[0]}\b",
                rf"(?:{frag}|{rng.choice(FRAGMENTS)})",
                rf"{frag.split()[0]}\s+\w+" if " " in frag else frag,
            ]
        )
        secondaries = None
        if rng.random() < 0.5:
            secondaries = [
                (rng.choice(FRAGMENTS), round(rng.uniform(0.1, 0.9), 2),
                 rng.choice([0, 3, 10, 50, 500]))
                for _ in range(rng.randrange(1, 3))
            ]
        sequences = None
        if rng.random() < 0.4:
            sequences = [
                (round(rng.uniform(0.1, 0.6), 2),
                 [rng.choice(FRAGMENTS) for _ in range(rng.randrange(1, 4))])
            ]
        context = rng.choice([None, (1, 1), (3, 5), (10, 10), (0, 0)])
        # exercise duplicate ids (shared frequency slots) and empty ids
        pid = rng.choice([f"p{i}", f"p{i}", f"p{i % 3}", ""])
        patterns.append(
            make_pattern(
                pid,
                regex=regex,
                confidence=round(rng.uniform(0.1, 1.0), 2),
                severity=rng.choice(severities),
                secondaries=secondaries,
                sequences=sequences,
                context=context,
            )
        )
    # split across two pattern sets to exercise set-major discovery order
    cut = max(1, n_patterns // 2)
    return [
        make_pattern_set(patterns[:cut], "libA"),
        make_pattern_set(patterns[cut:], "libB"),
    ]


def random_logs(rng: random.Random, n_lines: int) -> str:
    lines = []
    for _ in range(n_lines):
        r = rng.random()
        if r < 0.35:
            lines.append(rng.choice(NOISE))
        elif r < 0.7:
            frag = rng.choice(FRAGMENTS)
            lines.append(f"{rng.choice(['', 'ts=123 '])}{frag} happened")
        else:
            lines.append("filler " + "".join(rng.choice("abcdef ") for _ in range(20)))
    trailer = rng.choice(["", "\n", "\n\n"])
    return "\n".join(lines) + trailer


def assert_results_match(r1, r2):
    ev1 = [(e.line_number, e.matched_pattern.id, e.matched_pattern.name) for e in r1.events]
    ev2 = [(e.line_number, e.matched_pattern.id, e.matched_pattern.name) for e in r2.events]
    assert ev1 == ev2
    for a, b in zip(r1.events, r2.events):
        if np.isnan(b.score):
            assert np.isnan(a.score)
        else:
            assert a.score == pytest.approx(b.score, abs=TOL), (
                a.line_number, a.matched_pattern.id)
        assert a.context.to_dict() == b.context.to_dict()
    assert r1.summary.to_dict() == r2.summary.to_dict()
    assert r1.metadata.total_lines == r2.metadata.total_lines
    assert r1.metadata.patterns_used == r2.metadata.patterns_used


@pytest.mark.parametrize("seed", range(8))
def test_random_library_parity(seed):
    rng = random.Random(seed)
    sets = random_library(rng, rng.randrange(2, 8))
    config = ScoringConfig(
        frequency_threshold=rng.choice([2.0, 10.0]),
        proximity_max_window=rng.choice([5, 100]),
    )
    engine = AnalysisEngine(sets, config, clock=FakeClock())
    golden = GoldenAnalyzer(sets, config, clock=FakeClock())
    for _ in range(3):  # frequency state must evolve identically
        logs = random_logs(rng, rng.randrange(5, 120))
        data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=logs)
        assert_results_match(engine.analyze(data), golden.analyze(data))
    assert (
        engine.frequency.get_frequency_statistics()
        == golden.frequency.get_frequency_statistics()
    )


LONG_LITERALS = [
    # >31 positions: truncated on the bit tier (primary and secondary
    # roles), exact via the engine's host verify / distance repair
    "could not connect to server: Connection refused",
    "Back-off restarting failed container in pod sandbox",
    "A fatal error has been detected by the Java Runtime",
    "Liveness probe failed: HTTP probe failed with statuscode: 503",
]


def random_long_library(rng: random.Random, n_patterns: int):
    """Libraries whose primaries/secondaries include >31-char literals
    and literal-bearing alternations — the truncation + repair paths."""
    patterns = []
    for i in range(n_patterns):
        lit = rng.choice(LONG_LITERALS)
        regex = rng.choice(
            [
                lit,
                rf"(?:{lit}|{rng.choice(FRAGMENTS)})",
                rf"^{lit}",
                lit + r"\d*",
            ]
        )
        secondaries = None
        if rng.random() < 0.6:
            secondaries = [
                (rng.choice(LONG_LITERALS + FRAGMENTS),
                 round(rng.uniform(0.1, 0.9), 2),
                 rng.choice([3, 8, 100]))
                for _ in range(rng.randrange(1, 3))
            ]
        patterns.append(
            make_pattern(
                f"p{i}",
                regex=regex,
                confidence=round(rng.uniform(0.1, 1.0), 2),
                severity=rng.choice(["CRITICAL", "HIGH", "LOW"]),
                secondaries=secondaries,
            )
        )
    return [make_pattern_set(patterns, "liblong")]


def random_long_logs(rng: random.Random, n_lines: int) -> str:
    """Corpora that plant full long literals AND their 31-char prefixes
    (device-only false positives the engine must repair away)."""
    lines = []
    for _ in range(n_lines):
        r = rng.random()
        lit = rng.choice(LONG_LITERALS)
        if r < 0.25:
            lines.append(lit + rng.choice(["", " tail", "!"]))
        elif r < 0.5:
            # the poison case: exactly the truncated prefix, not the full
            lines.append(rng.choice(["", "pad "]) + lit[:31])
        elif r < 0.65:
            lines.append(rng.choice(FRAGMENTS) + " happened")
        else:
            lines.append("noise " + "".join(rng.choice("xyz ") for _ in range(12)))
    return "\n".join(lines) + rng.choice(["", "\n"])


@pytest.mark.parametrize("seed", range(6))
def test_random_long_literal_parity_bit_policy(seed):
    """Truncation + host verify/repair fuzz: long-literal libraries under
    the default tier plan (bit tier on, truncation active), corpora
    salted with prefix-only poison lines, engine vs golden over evolving
    frequency state."""
    rng = random.Random(31000 + seed)
    sets = random_long_library(rng, rng.randrange(2, 6))
    config = ScoringConfig(proximity_max_window=rng.choice([5, 100]))
    engine = AnalysisEngine(sets, config, clock=FakeClock())
    assert engine.matchers.bitglush is not None
    golden = GoldenAnalyzer(sets, config, clock=FakeClock())
    for _ in range(3):
        logs = random_long_logs(rng, rng.randrange(5, 80))
        data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=logs)
        assert_results_match(engine.analyze(data), golden.analyze(data))
    assert (
        engine.frequency.get_frequency_statistics()
        == golden.frequency.get_frequency_statistics()
    )


class TestEngineEdgeCases:
    def _pair(self, patterns, config=None):
        sets = [make_pattern_set(patterns)]
        cfg = config or ScoringConfig()
        return (
            AnalysisEngine(sets, cfg, clock=FakeClock()),
            GoldenAnalyzer(sets, cfg, clock=FakeClock()),
        )

    def run_both(self, patterns, logs, config=None):
        engine, golden = self._pair(patterns, config)
        data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=logs)
        r1, r2 = engine.analyze(data), golden.analyze(data)
        assert_results_match(r1, r2)
        return r1

    def test_empty_logs(self):
        r = self.run_both([make_pattern("a", regex="X")], "")
        assert r.metadata.total_lines == 1

    def test_only_newlines(self):
        r = self.run_both([make_pattern("a", regex="X")], "\n\n")
        assert r.metadata.total_lines == 0

    def test_no_patterns(self):
        r = self.run_both([], "ERROR something")
        assert r.events == []

    def test_match_on_empty_interior_line(self):
        # ^$ matches the empty line between content lines
        self.run_both([make_pattern("e", regex="^$")], "a\n\nb")

    def test_non_ascii_lines_host_verified(self):
        # 'a.c' DOES match 'aéc' in Java (é is one char) but the byte-level
        # DFA sees two bytes — the host-verify override must restore line 1
        r = self.run_both([make_pattern("dot", regex="a.c")], "aéc\naxc")
        assert [e.line_number for e in r.events] == [1, 2]

    def test_host_fallback_column(self):
        # state blowup -> DFA rejected -> host matcher column, same results
        engine, golden = self._pair(
            [make_pattern("blow", regex=r"[ab]*a[ab]{12}", confidence=0.5)]
        )
        assert engine.dfa_bank.n_regexes < engine.bank.n_columns
        logs = "\n".join(["ab" * 10, "b" * 30, "a" * 14])
        data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=logs)
        assert_results_match(engine.analyze(data), golden.analyze(data))

    def test_shared_pattern_ids_frequency_order(self):
        # two patterns with the same id interleave one frequency counter
        patterns = [
            make_pattern("dup", regex="AAA", confidence=1.0, severity="INFO"),
            make_pattern("dup", regex="BBB", confidence=1.0, severity="INFO"),
        ]
        config = ScoringConfig(frequency_threshold=1.0)
        logs = "\n".join(["AAA BBB", "AAA", "BBB", "AAA BBB"] + ["x"] * 4)
        self.run_both(patterns, logs, config)

    def test_empty_matching_secondary_ignores_padding_rows(self):
        """A secondary like ^$ matches zero-length padding rows; those are
        beyond n_lines and must not create phantom proximity hits."""
        pattern = make_pattern(
            "p", regex="OOM", confidence=1.0, severity="INFO",
            secondaries=[(r"^$", 0.5, 50)],
        )
        # 3 real lines (padded to 8 device rows), no blank line anywhere
        self.run_both([pattern], "x\nx\nOOM happened")

    def test_primary_less_pattern_with_bad_secondary_is_skipped(self):
        from log_parser_tpu.models.pattern import Pattern, SecondaryPattern
        bad = Pattern(
            id="frag", severity="HIGH",
            secondary_patterns=[SecondaryPattern(regex=r"a*+", weight=0.5)],
        )
        engine, golden = self._pair([bad, make_pattern("ok", regex="ERROR")])
        assert engine.skipped_patterns == golden.skipped_patterns
        assert [pid for pid, _ in engine.skipped_patterns] == ["frag"]

    def test_skipped_pattern_leaves_no_orphan_columns(self):
        patterns = [
            make_pattern("bad", regex="GOODPRIMARY",
                         secondaries=[("fine", 0.5, 10), (r"(?>x)", 0.5, 10)]),
            make_pattern("ok", regex="ERROR"),
        ]
        engine, _ = self._pair(patterns)
        interned = {c.regex for c in engine.bank.columns}
        assert "GOODPRIMARY" not in interned
        assert "fine" not in interned
        assert "ERROR" in interned

    def test_zero_window_hours_first_match_has_no_penalty(self):
        """window=0: the FIRST match of a pattern must take the 'no entry'
        early return (penalty 0), not the NaN formula path — and later
        matches go NaN, matching golden exactly."""
        config = ScoringConfig(frequency_time_window_hours=0)
        self.run_both(
            [make_pattern("oom", regex="OOM", confidence=1.0, severity="INFO")],
            "OOM here\nnothing\nOOM again\nx",
            config,
        )

    def test_negative_threshold_never_matched(self):
        """threshold<0 with no tracker entry: golden early-returns 0."""
        config = ScoringConfig(frequency_threshold=-1.0)
        self.run_both(
            [make_pattern("e", regex="ERR", confidence=1.0, severity="INFO")],
            "ERR one\nx\nERR two\nx",
            config,
        )

    def test_negative_context_windows_are_empty_slices(self):
        """lines_before/after < 0 behave as empty slices (golden Python
        slicing), so the context window is the matched line only."""
        from log_parser_tpu.models.pattern import ContextExtraction
        pattern = make_pattern("c", regex="MATCH", confidence=1.0, severity="INFO")
        pattern.context_extraction = ContextExtraction(lines_before=-5, lines_after=-2)
        self.run_both([pattern], "ERROR a\nERROR b\nMATCH ERROR\nERROR c")

    def test_overlong_line_host_verified(self):
        long_line = "x" * 5000 + " OutOfMemoryError"
        r = self.run_both(
            [make_pattern("oom", regex="OutOfMemoryError")], long_line + "\nshort"
        )
        assert [e.line_number for e in r.events] == [1]
