"""Sharded (shard_map) pipeline vs golden: multi-device parity on the
virtual 8-device CPU mesh — halo exchange, all_gather chains, cross-shard
frequency prefix, and shard-boundary window correctness."""

import dataclasses
import json
import os
import random
import re

import numpy as np
import pytest

from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.golden import GoldenAnalyzer
from log_parser_tpu.models import PodFailureData
from log_parser_tpu.parallel import ShardedEngine, make_mesh
from tests.conftest import FakeClock
from tests.helpers import make_pattern, make_pattern_set
from tests.test_engine_parity import assert_results_match, random_library, random_logs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh(8)


@pytest.mark.parametrize("seed", range(4))
def test_random_parity_small_batches(seed, mesh8):
    """Small logs: shards smaller than halos -> the all_gather fallback."""
    rng = random.Random(1000 + seed)
    sets = random_library(rng, rng.randrange(2, 6))
    config = ScoringConfig(frequency_threshold=rng.choice([2.0, 10.0]))
    engine = ShardedEngine(sets, config, mesh=mesh8, clock=FakeClock())
    golden = GoldenAnalyzer(sets, config, clock=FakeClock())
    for _ in range(2):
        logs = random_logs(rng, rng.randrange(5, 90))
        data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=logs)
        assert_results_match(engine.analyze(data), golden.analyze(data))
    assert (
        engine.frequency.get_frequency_statistics()
        == golden.frequency.get_frequency_statistics()
    )


def test_halo_path_large_batch(mesh8):
    """~1200 lines over 8 shards (Bl=256 > halo) -> ppermute halo path, with
    matches planted straddling every shard boundary."""
    patterns = [
        make_pattern(
            "oom", regex="OutOfMemoryError", confidence=0.9, severity="CRITICAL",
            secondaries=[("GC overhead", 0.6, 100)], context=(5, 5),
        ),
        make_pattern(
            "seq", regex="FAILURE", confidence=0.8, severity="HIGH",
            sequences=[(0.5, ["first thing", "second thing", "FAILURE"])],
        ),
    ]
    lines = [f"line {i}" for i in range(1200)]
    # matches exactly at and around the 8 x 256-row shard edges (256 rows
    # because 1200 pads to 2048... compute: next pow2 of 1200 is 2048 -> Bl=256)
    for edge in range(256, 2048, 256):
        if edge - 1 < 1200:
            lines[edge - 1] = "GC overhead spike"  # secondary on last row of shard
        if edge + 2 < 1200:
            lines[edge + 2] = "java.lang.OutOfMemoryError"  # primary 3 past edge
    lines[10] = "first thing"
    lines[400] = "second thing"
    lines[403] = "FAILURE detected"
    lines[500] = "ERROR context"
    lines[501] = "java.lang.OutOfMemoryError"
    logs = "\n".join(lines)
    sets = [make_pattern_set(patterns)]
    engine = ShardedEngine(sets, ScoringConfig(), mesh=make_mesh(8), clock=FakeClock())
    golden = GoldenAnalyzer(sets, ScoringConfig(), clock=FakeClock())
    data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=logs)
    r1, r2 = engine.analyze(data), golden.analyze(data)
    # 4 shard edges fall below line 1200 (256,512,768,1024) + oom@501 + seq
    assert len(r1.events) == 6  # every planted boundary match fired
    assert_results_match(r1, r2)


def test_single_device_mesh():
    patterns = [make_pattern("e", regex="ERROR", confidence=0.5, severity="LOW")]
    sets = [make_pattern_set(patterns)]
    engine = ShardedEngine(sets, ScoringConfig(), mesh=make_mesh(1), clock=FakeClock())
    golden = GoldenAnalyzer(sets, ScoringConfig(), clock=FakeClock())
    data = PodFailureData(pod={"metadata": {"name": "p"}}, logs="an ERROR\nok")
    assert_results_match(engine.analyze(data), golden.analyze(data))


def test_cross_shard_frequency_order(mesh8):
    """Matches of one pattern spread across shards must see a globally
    consistent read-before-record count order."""
    patterns = [make_pattern("rep", regex="REPEAT", confidence=1.0, severity="INFO")]
    sets = [make_pattern_set(patterns)]
    config = ScoringConfig(frequency_threshold=3.0)
    lines = ["x"] * 640
    for i in range(0, 640, 40):  # 16 matches spread over all shards
        lines[i] = "REPEAT hit"
    logs = "\n".join(lines)
    engine = ShardedEngine(sets, config, mesh=mesh8, clock=FakeClock())
    golden = GoldenAnalyzer(sets, config, clock=FakeClock())
    data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=logs)
    assert_results_match(engine.analyze(data), golden.analyze(data))


# ---------------------------------------------------------------------------
# The four-chip deployment: the shipped 83-pattern library on a 4-device
# mesh, against the benchmark's plain reference, on logs in the shape of
# the bulk traffic (benchmark/traffic/bulk_unique.json).

BUILTIN83 = os.path.join(REPO, "benchmark", "configs", "builtin83")
STAGES = ("device.upload", "device.launch", "device.wait", "device.readback")


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh(4)


@pytest.fixture(scope="module")
def builtin83():
    from log_parser_tpu.patterns import load_pattern_directory

    return load_pattern_directory(BUILTIN83)


def _bulk_logs(seed: int, k: int, n: int) -> list[str]:
    from benchmark.traffic import Traffic

    with open(os.path.join(REPO, "benchmark", "traffic", "bulk_unique.json")) as f:
        spec = json.load(f)
    return Traffic(spec, {}, seed).logs(0, k, n).split("\n")


def _plant_at_edges(lines: list[str], edges: list[int]) -> None:
    """Hits whose secondaries, sequence events and context windows lie on
    both sides of a shard edge (``e`` is the first line of a shard)."""
    e1, e2 = edges[0], edges[1]
    lines[e1 - 4] = "ERROR allocation failed"
    lines[e1 - 3] = "[Full GC (Ergonomics) 255M->250M(256M), 0.41 secs]"
    lines[e1 - 1] = "GC overhead limit exceeded"
    lines[e1 + 1] = "java.lang.OutOfMemoryError: Java heap space"
    lines[e1 + 2] = "    at com.example.Service.handle(Service.java:42)"
    lines[e1 + 3] = "WARN heap still full"
    lines[e2 - 6] = "retrying connection to db in 2s"
    lines[e2 - 2] = "dial tcp 10.0.0.7:5432: i/o timeout"
    lines[e2] = "dial tcp 10.0.0.7:5432: Connection refused"
    # an unbounded backward sequence: its first event two shards back
    lines[3] = "Migration V3__add_index failed"
    lines[-5] = "Migration V4__add_column failed"
    lines[-3] = "Application run failed"


def _reference(requests: list[str], scoring: dict) -> list[tuple[int, list]]:
    """The plain reference's answers, the frequency penalty carried over
    the requests in the order given."""
    from benchmark import reference as ref

    lib = ref.Library(BUILTIN83)
    scorer = ref.Scorer(lib, scoring)
    freq = ref.Frequency(scoring)
    out = []
    for logs in requests:
        n, events = ref.analyze(lib, scorer, logs)
        out.append((n, [(line, pid, freq.score(pid, base), digest)
                        for line, pid, base, digest in events]))
    return out


def _served(result) -> tuple[int, list]:
    from benchmark.loadgen import served_events

    return served_events(json.dumps(result.to_dict(drop_none=True)).encode())


def _assert_same_answers(engine, requests, scoring) -> None:
    got = [_served(engine.analyze(PodFailureData(pod={"metadata": {"name": "p"}},
                                                 logs=logs)))
           for logs in requests]
    want = _reference(requests, scoring)
    for (n_got, ev_got), (n_want, ev_want) in zip(got, want):
        assert n_got == n_want
        assert [(e[0], e[1], e[3]) for e in ev_got] == [
            (e[0], e[1], e[3]) for e in ev_want]
        for g, w in zip(ev_got, ev_want):
            assert abs(g[2] - w[2]) <= 1e-9, (g, w)


def _edges(engine, n_lines: int) -> list[int]:
    from log_parser_tpu.native.ingest import Corpus

    B = Corpus("\n".join(["x"] * n_lines),
               min_rows=engine._corpus_min_rows()).encoded.u8.shape[0]
    D = engine.step.n_shards
    return [d * (B // D) for d in range(1, D) if d * (B // D) < n_lines]


@pytest.mark.parametrize("seed", [4000000007, 2**31 + 11])
def test_builtin83_hits_straddling_shard_edges_match_reference(
        seed, mesh4, builtin83):
    """Secondary, sequence and context windows that cross shard edges, in
    seeded bulk-shaped logs, give the reference's events and scores."""
    engine = ShardedEngine(builtin83, ScoringConfig(), mesh=mesh4,
                           clock=FakeClock())
    lines = _bulk_logs(seed, 0, 3000)
    edges = _edges(engine, len(lines))
    assert len(edges) == 2 and engine.step.h_prox < edges[0]  # halo path
    _plant_at_edges(lines, edges)
    scoring = dataclasses.asdict(ScoringConfig())
    _assert_same_answers(engine, ["\n".join(lines)], scoring)
    events = engine.analyze(PodFailureData(pod={}, logs="\n".join(lines))).events
    planted = {ev.line_number - 1 for ev in events}
    assert {edges[0] + 1, edges[1], len(lines) - 5} <= planted


def test_builtin83_frequency_carries_over_requests_in_finalize_order(
        mesh4, builtin83):
    """Four requests on one engine: each match's frequency penalty reads
    the counts every earlier request recorded, as the reference's do."""
    config = ScoringConfig(frequency_threshold=2.0)
    engine = ShardedEngine(builtin83, config, mesh=mesh4, clock=FakeClock())
    requests = ["\n".join(_bulk_logs(977, k, 2500)) for k in range(4)]
    _assert_same_answers(engine, requests, dataclasses.asdict(config))


def _error_storm(n_lines: int, storm: int) -> tuple[list, str]:
    """One ``ERROR`` pattern and a log whose first ``storm`` lines all
    match: one shard holds them all."""
    sets = [make_pattern_set([make_pattern("e", regex="ERROR", confidence=0.5,
                                           severity="LOW")])]
    lines = ["ERROR boom"] * storm + ["fine"] * (n_lines - storm)
    return sets, "\n".join(lines)


def test_k_ladder_relaunch_gives_the_same_records_and_is_counted(mesh4):
    from log_parser_tpu.ops.fused import K_LADDER

    storm = K_LADDER[0] + 904  # one shard overflows the first bucket
    sets, logs = _error_storm(4 * 8192, storm)
    engine = ShardedEngine(sets, ScoringConfig(), mesh=mesh4, clock=FakeClock())
    from log_parser_tpu.native.ingest import Corpus

    enc = Corpus(logs, min_rows=engine._corpus_min_rows()).encoded
    B, C = enc.u8.shape[0], engine.bank.n_columns
    zeros = np.zeros((B, C), dtype=bool)
    args = (enc.u8, enc.lengths, zeros, zeros, 4 * 8192)
    cold = engine.step(*args, k_hint=0)
    warm = engine.step(*args, k_hint=4 * K_LADDER[1])
    assert (cold.launches, warm.launches) == (2, 1)
    # the second rung, capped at a shard's 8,192 rows x 1 pattern
    assert cold.k_local == warm.k_local == min(K_LADDER[1], 8192)
    assert cold.records.n_matches == warm.records.n_matches == storm
    for field in ("line", "pattern", "sec_dist", "seq_ok", "ctx_counts"):
        np.testing.assert_array_equal(getattr(cold.records, field),
                                      getattr(warm.records, field))

    engine._k_hint = 0
    golden = GoldenAnalyzer(sets, ScoringConfig(), clock=FakeClock())
    data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=logs)
    assert_results_match(engine.analyze(data), golden.analyze(data))
    obs = engine.obs
    assert obs.shard_relaunches.value(tenant="default") == 1
    assert obs.shard_record_slots.value(tenant="default") == 4 * 8192
    assert obs.shard_records.value(tenant="default") == storm


def _seq_library():
    return [make_pattern_set([
        make_pattern(
            "oom", regex="OutOfMemoryError", confidence=0.9, severity="CRITICAL",
            secondaries=[("GC overhead", 0.6, 100)], context=(5, 5),
        ),
        make_pattern(
            "seq", regex="FAILURE", confidence=0.8, severity="HIGH",
            sequences=[(0.5, ["first thing", "second thing", "FAILURE"])],
        ),
    ])]


def test_exchange_bytes_counter_equals_hand_computed_value(mesh4):
    """1,200 lines pad to 2,048 rows, 512 a shard on 4 shards. One
    secondary column with a 100-line window: 100 bool rows each way
    from 3 senders, 2 * 3 * 100 * 1 = 600 B. Context flags, a 5-line
    window: 5 rows of four int32 each way, 2 * 3 * 5 * 16 = 480 B. Three
    sequence event columns, all_gathered: each of 4 shards receives the
    other 3 shards' 512 bool rows, 4 * 3 * 512 * 3 = 18,432 B."""
    engine = ShardedEngine(_seq_library(), ScoringConfig(), mesh=mesh4,
                           clock=FakeClock())
    logs = "\n".join(["line"] * 1199 + ["java.lang.OutOfMemoryError"])
    engine.analyze(PodFailureData(pod={"metadata": {"name": "p"}}, logs=logs))
    assert engine.obs.shard_exchange_bytes.value(tenant="default") == (
        600 + 480 + 18432)
    assert engine.obs.shard_relaunches.value(tenant="default") == 0


_COLLECTIVE = re.compile(
    r'"stablehlo\.(collective_permute|all_gather)".*?'
    r'(?:source_target_pairs = dense<(\[\[.*?\]\])>|replica_groups = dense<(\[\[.*?\]\])>)'
    r'.*?: \(tensor<([0-9x]+)x(i1|i32)>\) -> tensor<([0-9x]+)x(?:i1|i32)>'
)


@pytest.mark.parametrize("n_lines", [60, 1200], ids=["gather", "halo"])
def test_exchange_bytes_equal_the_lowered_collectives(mesh4, n_lines):
    """The static count agrees with the collectives the lowered SPMD
    program holds: pairs × operand bytes for each ``collective_permute``,
    group × (result − operand) bytes for each ``all_gather``. 60 lines
    give 16-row shards, shorter than the halos, so every family gathers."""
    import jax
    import jax.numpy as jnp

    from log_parser_tpu.native.ingest import Corpus

    engine = ShardedEngine(_seq_library(), ScoringConfig(), mesh=mesh4,
                           clock=FakeClock())
    enc = Corpus("\n".join(["x"] * n_lines),
                 min_rows=engine._corpus_min_rows()).encoded
    B, T = enc.u8.shape
    C = engine.bank.n_columns
    text = engine.step._jit.lower(
        4096,
        jax.ShapeDtypeStruct((B, T), jnp.uint8),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B, C), jnp.bool_),
        jax.ShapeDtypeStruct((B, C), jnp.bool_),
        jax.ShapeDtypeStruct((), jnp.int32),
    ).as_text()
    itemsize = {"i1": 1, "i32": 4}
    total = 0
    for m in _COLLECTIVE.finditer(text):
        kind, pairs, groups, src, dtype, dst = m.groups()
        src_b = int(np.prod([int(x) for x in src.split("x")])) * itemsize[dtype]
        if kind == "collective_permute":
            total += len(json.loads(pairs)) * src_b
        else:
            dst_b = int(np.prod([int(x) for x in dst.split("x")])) * itemsize[dtype]
            total += len(json.loads(groups)[0]) * (dst_b - src_b)
    assert total > 0
    assert engine.step.exchange_bytes(B) == total


def test_sharded_step_records_the_device_stages(mesh4):
    engine = ShardedEngine(_seq_library(), ScoringConfig(), mesh=mesh4,
                           clock=FakeClock())
    logs = "first thing\nsecond thing\nFAILURE now\njava.lang.OutOfMemoryError"
    engine.analyze(PodFailureData(pod={"metadata": {"name": "p"}}, logs=logs))
    stages = engine.last_trace.stage_dict()
    assert set(STAGES) <= set(stages)
    assert all(stages[s] > 0 for s in STAGES)
    scraped = engine.obs.registry.render()
    for s in STAGES:
        assert f'stage="{s}"' in scraped
