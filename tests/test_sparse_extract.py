"""The line-cache path's sparse host extraction against the device's.

Each case serves the same requests through a cache-off engine (whose
records come from the device program's own extraction) and a cache-on
engine (whose records ``linecache.records_from_hits`` builds on the host
from sparse ``(line, col)`` hit coordinates), and requires the two
``MatchRecords`` to agree field for field and row for row. Requests are
served twice on the cache-on engine, so its records are built once from
readback rows and once from packed cache rows. Every case also checks
that ``logparser_extract_hit_coords_total`` advanced by the number of
set post-override bits.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.models.pod import PodFailureData
from log_parser_tpu.native.ingest import Corpus
from log_parser_tpu.runtime import AnalysisEngine
from log_parser_tpu.runtime.linecache import bool_hits

from helpers import make_pattern, make_pattern_set

HIT_COORDS = "logparser_extract_hit_coords_total"
SERVICES = ("auth", "db", "cache")


def _synth_sets():
    """Many patterns, few distinct secondary columns: every third
    pattern's secondary is one of three shared ``<svc> degraded``
    regexes, as in a generated 10k-pattern library."""
    pats = []
    for i in range(24):
        svc = SERVICES[i % 3]
        pats.append(
            make_pattern(
                f"svc{i}",
                regex=f"svc{i} failed",
                confidence=0.5 + 0.01 * i,
                secondaries=(
                    [(f"{svc} degraded", 0.3, 10)] if i % 3 == 0 else None
                ),
                context=(3, 2) if i % 4 == 0 else None,
            )
        )
    return [make_pattern_set(pats)]


def _synth_line(rng: random.Random) -> str:
    roll = rng.random()
    if roll < 0.15:
        return f"svc{rng.randrange(24)} failed at step {rng.randrange(4)}"
    if roll < 0.25:
        return f"{rng.choice(SERVICES)} degraded"
    if roll < 0.30:
        return "ERROR upstream reset"
    if roll < 0.34:
        return "WARN retrying" if roll < 0.32 else "WARN then ERROR"
    if roll < 0.37:
        return "    at com.example.Worker.run(Worker.java:42)"
    if roll < 0.40:
        return "java.lang.IllegalStateException: closed"
    return f"tick {rng.randrange(40)}"


def _seq_sets():
    """Sequences, context windows and a secondary on one pattern."""
    return [
        make_pattern_set(
            [
                make_pattern(
                    "oom",
                    regex="OutOfMemoryError",
                    confidence=0.9,
                    severity="CRITICAL",
                    secondaries=[("GC overhead", 0.3, 10)],
                    sequences=[(1.5, ["Full GC", "OutOfMemoryError"])],
                    context=(2, 2),
                ),
                make_pattern(
                    "conn",
                    regex="Connection refused",
                    confidence=0.7,
                    sequences=[(1.3, ["Full GC", "slow disk"])],
                ),
                make_pattern("fatal", regex="FATAL", confidence=0.8),
            ]
        )
    ]


SEQ_TEMPLATES = (
    "INFO steady-state heartbeat",
    "Full GC pause",
    "GC overhead limit reached",
    "java.lang.OutOfMemoryError: heap",
    "dial tcp 10.0.0.1: Connection refused",
    "FATAL disk controller",
    "WARN slow disk",
    "ERROR after WARN",
)


def _seq_line(rng: random.Random) -> str:
    if rng.random() < 0.6:
        return rng.choice(SEQ_TEMPLATES)
    return f"INFO tick {rng.randrange(30)}"


def _random_logs(line_fn, seed: int, n_requests: int = 3) -> list[str]:
    rng = random.Random(seed)
    return [
        "\n".join(line_fn(rng) for _ in range(rng.randrange(20, 90)))
        for _ in range(n_requests)
    ]


CASES = {
    "synth_shared_secondaries_s1": (_synth_sets, _random_logs(_synth_line, 1)),
    "synth_shared_secondaries_s2": (_synth_sets, _random_logs(_synth_line, 2)),
    "sequences_context_s1": (_seq_sets, _random_logs(_seq_line, 11)),
    "sequences_context_s2": (_seq_sets, _random_logs(_seq_line, 12)),
    # hits on the first and last line, adjacent secondary hits
    "edges_and_adjacent": (_seq_sets, [
        "GC overhead limit reached\n"
        "java.lang.OutOfMemoryError: heap\n"
        "GC overhead limit reached\n"
        "GC overhead limit reached\n"
        "INFO steady\n"
        "Full GC pause\n"
        "java.lang.OutOfMemoryError: heap",
        "java.lang.OutOfMemoryError: heap\nINFO a\nGC overhead limit reached",
        # the sequence's last event 5 lines from the primary (inside the
        # near window), then 6 lines away on both sides (outside it)
        "Full GC pause\nslow disk\nx1\nx2\nx3\nx4\nConnection refused",
        "Full GC pause\nslow disk\nx1\nx2\nx3\nx4\nx5\n"
        "Connection refused\ny1\ny2\ny3\ny4\ny5\nslow disk",
    ]),
    # the primary line is also a secondary hit: its own row is excluded
    "own_row_secondary": (
        lambda: [make_pattern_set([
            make_pattern(
                "oom", regex="OutOfMemoryError",
                secondaries=[("Error", 0.4, 5)], context=(1, 1),
            ),
        ])],
        [
            "java.lang.OutOfMemoryError: heap\nINFO x\nsome Error",
            "INFO a\njava.lang.OutOfMemoryError\njava.lang.OutOfMemoryError",
        ],
    ),
    # two patterns interned onto one primary column
    "shared_primary_column": (
        lambda: [make_pattern_set([
            make_pattern("first", regex="disk full", confidence=0.6),
            make_pattern("mid", regex="ERROR", confidence=0.5),
            make_pattern(
                "second", regex="disk full", confidence=0.7,
                secondaries=[("ERROR", 0.2, 4)],
            ),
        ])],
        ["ERROR x\ndisk full\nINFO\ndisk full ERROR", "disk full"],
    ),
    # a needs_host (non-ASCII) line and a host-only (lookbehind) column;
    # on "cafés" the device's bytes match "caf..s" and the host's
    # characters do not, so the splice must drop a set bit
    "override_splice": (
        lambda: [make_pattern_set([
            make_pattern("lb", regex=r"(?<=refused )connection",
                         confidence=0.8, secondaries=[("retry", 0.2, 6)]),
            make_pattern("conn", regex="Connection refused", confidence=0.7),
            make_pattern("cafe", regex="caf..s", confidence=0.6),
        ])],
        [
            "dial: refused connection\nretry 1\n"
            "INFO café ☃ Connection refused\nrefused connection ☃",
            "INFO café ☃ Connection refused\nretry 2\norder cafés now",
        ],
    ),
    "empty_request": (_seq_sets, ["", "\n", "INFO only"]),
}


def _pod(logs: str) -> PodFailureData:
    return PodFailureData(pod={"metadata": {"name": "sx"}}, logs=logs)


def _set_bits(engine: AnalysisEngine, logs: str) -> int:
    """Post-override set bits of the whole request, read off the dense
    device cube (the count the extract's coordinates must equal)."""
    corpus = Corpus(logs, min_rows=engine._corpus_min_rows())
    n = corpus.n_lines
    if n == 0:
        return 0
    ov = engine._overrides(corpus)
    om, val = ov if ov is not None else (None, None)
    enc = corpus.encoded
    bits = engine.fused.cube_rows(enc.u8, enc.lengths, n, om, val)
    return int(np.asarray(bits)[:n].sum())


@pytest.mark.parametrize("case", sorted(CASES))
def test_sparse_extract_matches_device(case):
    make_sets, requests = CASES[case]
    device = AnalysisEngine(make_sets(), ScoringConfig())
    cached = AnalysisEngine(make_sets(), ScoringConfig())
    cached.enable_line_cache(4.0)
    registry = cached.obs.registry
    # first pass: readback rows; second pass: packed cache rows
    for logs in requests + requests:
        want = device._prepare(_pod(logs)).recs
        before = registry.value(HIT_COORDS, tenant="default")
        got = cached._prepare(_pod(logs)).recs
        coords = registry.value(HIT_COORDS, tenant="default") - before
        m = want.n_matches
        assert got.n_matches == m
        for field in ("line", "pattern", "sec_dist", "seq_ok", "ctx_counts"):
            np.testing.assert_array_equal(
                getattr(got, field)[:m], getattr(want, field)[:m],
                err_msg=f"{case}: {field}",
            )
        assert coords == _set_bits(cached, logs)
    assert cached.line_cache.stats()["hits"] > 0 or case == "empty_request"
    assert f'{HIT_COORDS}{{tenant="default"}}' in registry.render()



def _layout(dense: np.ndarray, layout: str) -> np.ndarray:
    if layout == "f":
        return np.asfortranarray(dense)
    if layout == "f_row_slice":  # rows sliced off a column-major matrix
        return np.asfortranarray(np.vstack([dense, dense]))[: len(dense)]
    if layout == "strided":
        big = np.zeros((2 * dense.shape[0], 2 * dense.shape[1]), dtype=bool)
        big[::2, ::2] = dense
        return big[::2, ::2]
    return dense


@pytest.mark.parametrize("layout", ["c", "f", "f_row_slice", "strided"])
def test_bool_hits_any_memory_layout(layout):
    """A readback matrix may come in any memory order; its hits are the
    same, sorted by row then column."""
    dense = np.random.default_rng(7).random((37, 29)) < 0.08
    got_r, got_c = bool_hits(_layout(dense, layout))
    want_r, want_c = np.nonzero(dense)
    np.testing.assert_array_equal(got_r, want_r)
    np.testing.assert_array_equal(got_c, want_c)
