"""The plain reference gives golden's answers, and its literal gate and
hit lists change nothing but its speed."""

from __future__ import annotations

import dataclasses

import pytest

from benchmark import reference as ref
from benchmark.cell import load_cell
from benchmark.libraries import library_dir
from benchmark.tests import tiny
from benchmark.traffic import WARMUP, WINDOW, Traffic

CELLS = ["builtin83.bulk_unique", "builtin83.bulk_rr90",
         "builtin83.triage_open", "synth10k.bulk_unique"]


def _requests(cell, seed, n):
    t = Traffic(cell.traffic, cell.config, seed)
    sizes = t.warmup_sizes()
    out = [t.logs(WARMUP, k, s) for k, s in enumerate(sizes[:2])]
    size = t.open_plan(2.0)[1] if t.open_loop else [t.size(k) for k in range(n)]
    return out + [t.logs(WINDOW, k, size[k]) for k in range(n)]


@pytest.mark.parametrize("name", CELLS)
def test_reference_is_golden(name):
    """Events, contexts and float64 scores equal golden/engine.py's,
    frequency state carried across the requests in order."""
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.golden import GoldenAnalyzer
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.patterns import load_pattern_directory

    cell = tiny.shrink(load_cell(name))
    lib_dir = library_dir(cell.config)
    golden = GoldenAnalyzer(load_pattern_directory(lib_dir),
                            dataclasses.replace(ScoringConfig()))
    lib = ref.Library(lib_dir)
    scorer = ref.Scorer(lib, cell.config["scoring"])
    freq = ref.Frequency(cell.config["scoring"])
    n_events = 0
    for logs in _requests(cell, 9000000013, 6):
        want = golden.analyze(PodFailureData(pod={"metadata": {"name": "p"}},
                                             logs=logs))
        n_lines, parts = ref.analyze(lib, scorer, logs)
        got = [(line, pid, freq.score(pid, base), dig)
               for line, pid, base, dig in parts]
        assert n_lines == want.metadata.total_lines
        assert [(g[0], g[1], g[2]) for g in got] == [
            (e.line_number, e.matched_pattern.id, e.score) for e in want.events
        ]
        assert [g[3] for g in got] == [
            ref.context_digest(e.context.lines_before, e.context.matched_line,
                               e.context.lines_after)
            for e in want.events
        ]
        n_events += len(got)
    assert n_events > 0


@pytest.mark.parametrize("name", CELLS)
def test_gated_matcher_is_the_plain_one(name):
    cell = tiny.shrink(load_cell(name))
    lib = ref.Library(library_dir(cell.config))
    for logs in _requests(cell, 9000000017, 3):
        lines = ref.java_split_lines(logs)
        assert ref.match(lib, lines) == ref.match_plain(lib, lines)


@pytest.mark.parametrize("regex, lits", [
    ("OutOfMemoryError: Metaspace|GC overhead", ["GC overhead", "OutOfMemoryError: Metaspace"]),
    ("FATAL:\\s+(sorry, )?too many (connections|clients)", ["too many "]),
    ("connect\\(\\) failed .* while", ["connect() failed "]),
    ("colou?r scheme", ["r scheme"]),
    ("ab+c{2}dddd", ["dddd"]),
    ("x[0-9]+yz? end", [" end"]),
    ("(?i)timeout", None),
    ("abc|x", None),
    ("\\x41BCD", None),
])
def test_required_literals(regex, lits):
    assert ref.required_literals(regex) == lits


def test_literals_are_contained_in_every_match():
    import random
    import re as _re

    rng = random.Random(3)
    regexes = ["colou?r scheme", "FATAL:\\s+(sorry, )?too many (connections|clients)",
               "ab+c{2}dddd", "x[0-9]+yz? end", "Heap dump file created|Dumping heap to"]
    alphabet = "abcdxyz colour scheme FATAL: sorry, too many connections0123 end"
    for regex in regexes:
        lits = ref.required_literals(regex)
        pat = _re.compile(regex)
        for _ in range(3000):
            s = "".join(rng.choice(alphabet) for _ in range(rng.randint(0, 40)))
            for probe in (s, s + regex.replace("\\", "")):
                if pat.search(probe):
                    assert any(lit in probe for lit in lits), (regex, probe)
