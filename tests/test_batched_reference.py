"""The micro-batched deployment (``benchmark/configs/builtin83_batched.json``)
on the CPU: the shipped 83-pattern library served with the micro-batcher
and the line cache behind ``make_server``, driven by concurrent seeded
bursts in the shape of the ``flush_ticks`` arrival process, every answer
compared with the benchmark's plain reference replayed in the order the
engine finalized the requests. Also what the flushes record on
``/metrics``, how a flush's residual keeps to its width's compiled row
rungs, and the traffic pieces the deployment's cells add."""

from __future__ import annotations

import json
import math
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.runtime import AnalysisEngine
from log_parser_tpu.serve import make_server

from helpers import make_pattern, make_pattern_set

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUILTIN83 = os.path.join(REPO, "benchmark", "configs", "builtin83")
TRAFFIC = os.path.join(REPO, "benchmark", "traffic")
SEED = 2**31 + 77
# On a CPU shared with other test workers serve's 2 ms window coalesces
# by chance; a wider one makes every burst share flushes. The batching
# semantics are the same at any wait.
WAIT_MS = 100.0
# lines every request repeats, so a flush's cross-request dedup serves
# some rows of one request from another's
HEARTBEATS = [
    "2026-07-29T07:00:00Z INFO liveness ok",
    "2026-07-29T07:00:00Z INFO readiness ok",
    "2026-07-29T07:00:00Z DEBUG flushed 0 records",
]


def _spec(name: str) -> dict:
    with open(os.path.join(TRAFFIC, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def _config() -> dict:
    path = os.path.join(REPO, "benchmark", "configs", "builtin83_batched.json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _burst_traffic():
    """``tail_flush``'s mix at tails of 20–300 lines, with heartbeats
    every request shares and failure lines 5× as dense."""
    from benchmark.traffic import Traffic

    spec = _spec("tail_flush")
    spec["lines"] = {"log_uniform": [20, 300]}
    spec["mix"][1]["weight"] = 0.05
    spec["mix"].append({"weight": 0.2, "pick": HEARTBEATS})
    return Traffic(spec, _config(), SEED)


class _Deployment:
    def __init__(self):
        from log_parser_tpu.patterns import load_pattern_directory

        self.engine = AnalysisEngine(load_pattern_directory(BUILTIN83),
                                     ScoringConfig())
        self.engine.enable_batching(wait_ms=WAIT_MS)
        self.engine.enable_line_cache(_config()["serve"]["line_cache_mb"])
        self.server = make_server(self.engine, "127.0.0.1", 0)
        self.port = self.server.server_address[1]
        self.thread = threading.Thread(target=self.server.serve_forever,
                                       daemon=True)
        self.thread.start()
        # (members of the flush, residual dispatch shape), in order
        self.dispatched: list[tuple[int, tuple[int, int]]] = []
        self.members = 0
        run_cube = self.engine._run_cube
        cached_batch = self.engine.batcher._cached_batch

        def counted(items, cache, stages):
            self.members = len(items)
            return cached_batch(items, cache, stages)

        def recorded(lines_u8, lengths, n_rows, trace=None):
            self.dispatched.append((self.members, lines_u8.shape))
            return run_cube(lines_u8, lengths, n_rows, trace=trace)

        self.engine.batcher._cached_batch = counted
        self.engine._run_cube = recorded

    def close(self):
        self.server.shutdown()
        self.server.server_close()
        self.thread.join(timeout=30)
        self.engine.batcher.close()


@pytest.fixture(scope="module")
def burst_run():
    """Every tick of a ``flush_ticks`` plan posted at once, tick after
    tick, each request from its own thread; then the answers, the
    finalize order and the ``/metrics`` before and after."""
    from benchmark.loadgen import post
    from benchmark.server import parse_metrics
    from benchmark.traffic import WINDOW, plugin, request_id

    dep = _Deployment()
    traffic = _burst_traffic()
    loop = dict(traffic.spec["loop"], rate_per_s=24.0)
    due, sizes = plugin("arrivals", "flush_ticks").plan(
        loop, traffic.quantile_sizes, SEED, 3.0)
    bodies = [json.dumps(traffic.body(WINDOW, k, n)).encode()
              for k, n in enumerate(sizes)]
    # one lone request first: its flush compiles the width's rung ladder
    warm = json.dumps(traffic.body(WINDOW, len(sizes), 300)).encode()
    assert post(dep.port, request_id(WINDOW, len(sizes)), warm)[0] == 200
    compiled_before = dep.engine.fused._jit_cube_plain._cache_size()
    scrape = dep.engine.obs.registry.render
    before = parse_metrics(scrape())
    answers = {}
    try:
        with ThreadPoolExecutor(max_workers=32) as pool:
            for t in sorted(set(due)):
                ks = [k for k, d in enumerate(due) if d == t]
                got = pool.map(lambda k: post(dep.port, request_id(WINDOW, k),
                                              bodies[k]), ks)
                for k, (status, raw, _) in zip(ks, got):
                    answers[request_id(WINDOW, k)] = (status, raw)
        # a flush's demux is observed once its last member is answered
        demuxed = dep.engine.obs.batch_flush_seconds.snapshot
        deadline = time.monotonic() + 30
        while (demuxed(tenant=dep.engine.obs_tenant, part="demux")[2]
               < dep.engine.batcher.batches_flushed):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        after = parse_metrics(scrape())
        order = sorted((e["seq"], e["requestId"], e.get("outcome", ""))
                       for e in dep.engine.obs.ring.recent())
        yield {"engine": dep.engine, "dispatched": list(dep.dispatched),
               "traffic": traffic, "sizes": sizes + [300], "answers": answers,
               "before": before, "after": after, "order": order,
               "ring": dep.engine.obs.ring.recent(),
               "compiled_before": compiled_before,
               "compiled": dep.engine.fused._jit_cube_plain._cache_size()}
    finally:
        dep.close()


def _delta(run, name, **labels) -> float:
    want = set(labels.items())

    def total(scrape):
        return sum(v for (n, ls), v in scrape.items()
                   if n == name and want <= set(ls))

    return total(run["after"]) - total(run["before"])


def test_every_answer_equals_the_reference_in_finalize_order(burst_run):
    from benchmark import reference as ref
    from benchmark.loadgen import served_events
    from benchmark.traffic import parse_request_id

    scoring = _config()["scoring"]
    lib = ref.Library(BUILTIN83)
    scorer = ref.Scorer(lib, scoring)
    freq = ref.Frequency(scoring)
    answers, traffic = burst_run["answers"], burst_run["traffic"]
    assert all(status == 200 for status, _ in answers.values())
    finalized = [rid for _, rid, outcome in burst_run["order"] if outcome == "ok"]
    # the lone warm-up request and every request of the bursts, once each
    assert sorted(finalized) == sorted(list(answers) + [finalized[0]])
    events_seen = 0
    for rid in finalized:
        stream, k = parse_request_id(rid)
        n, events = ref.analyze(lib, scorer,
                                traffic.logs(stream, k, burst_run["sizes"][k]))
        want = [(line, pid, freq.score(pid, base), digest)
                for line, pid, base, digest in events]
        if rid not in answers:
            continue
        got_n, got = served_events(answers[rid][1])
        assert got_n == n, rid
        assert [(e[0], e[1], e[3]) for e in got] == [
            (e[0], e[1], e[3]) for e in want], rid
        for g, w in zip(got, want):
            assert abs(g[2] - w[2]) <= 1e-9, (rid, g, w)
        events_seen += len(got)
    assert events_seen > 0
    assert _delta(burst_run, "logparser_fallback_total") == 0


def test_flushes_coalesce_and_count_their_residual(burst_run):
    flushes = _delta(burst_run, "logparser_batches_flushed_total")
    batched = _delta(burst_run, "logparser_requests_batched_total")
    assert flushes > 0
    assert batched == len(burst_run["answers"])
    # at least one flush carried more than one member
    assert batched > flushes
    rows = _delta(burst_run, "logparser_batch_residual_rows_total")
    slots = _delta(burst_run, "logparser_batch_residual_slots_total")
    assert 0 < rows <= slots
    # the heartbeats repeat across requests: the residual holds each once
    lines = sum(burst_run["sizes"][:-1])
    assert rows < lines
    assert _delta(burst_run, "logparser_batch_flush_seconds_count",
                  part="device") == flushes
    assert _delta(burst_run, "logparser_batch_flush_seconds_count",
                  part="demux") == flushes


def test_phase_totals_count_a_flush_device_step_once(burst_run):
    flushes = _delta(burst_run, "logparser_batches_flushed_total")
    device_n = _delta(burst_run, "logparser_phase_seconds_count",
                      phase="device", route="batched")
    device_s = _delta(burst_run, "logparser_phase_seconds_sum",
                      phase="device", route="batched")
    assert device_n == flushes
    assert device_s == pytest.approx(
        _delta(burst_run, "logparser_batch_flush_seconds_sum", part="device"),
        rel=1e-9)
    # the stages of the flush's device step, once a flush too
    assert _delta(burst_run, "logparser_stage_seconds_count",
                  stage="cache.populate") <= flushes
    assert _delta(burst_run, "logparser_stage_seconds_count",
                  stage="device.wait") <= flushes
    # each member's own trace still shows the step it shared
    members = sum(e["phasesMs"].get("device", 0.0) for e in burst_run["ring"]
                  if e["requestId"] in burst_run["answers"]) / 1e3
    assert members > device_s
    # the handler's own stages are still one per request
    assert _delta(burst_run, "logparser_stage_seconds_count",
                  stage="engine.frequency") == len(burst_run["answers"])


def test_flush_residuals_keep_to_row_rungs_already_run(burst_run):
    """After the first flush at a width no residual compiles: a lone
    request's residual runs at its own rung, as unbatched, and a flush of
    several requests keeps to dispatches of at most FLUSH_CHUNK_ROWS rows,
    every one of them a rung of the width's compiled ladder."""
    from log_parser_tpu.ops.encode import _pad_rows
    from log_parser_tpu.runtime.batcher import FLUSH_CHUNK_ROWS

    dispatched = burst_run["dispatched"]
    assert any(members > 1 for members, _ in dispatched)
    for members, (rows, _width) in dispatched:
        assert rows == _pad_rows(rows, 8)
        if members > 1:
            assert rows <= FLUSH_CHUNK_ROWS, (members, rows)
    # the bursts compiled nothing the warm-up's first flush had not
    assert burst_run["compiled"] == burst_run["compiled_before"]


def _tiny_batcher(wait_ms: float = 2.0):
    engine = AnalysisEngine(
        [make_pattern_set([make_pattern("oom", regex="OutOfMemoryError")])],
        ScoringConfig())
    return engine.enable_batching(wait_ms=wait_ms)


@pytest.mark.parametrize("u,lone,chunks", [
    (1, False, [(1, 8)]),
    (100, False, [(100, 128)]),
    (2048, False, [(2048, 2048)]),
    (2049, False, [(2048, 2048), (1, 8)]),
    (4096, False, [(2048, 2048), (2048, 2048)]),
    (5000, False, [(2048, 2048), (2048, 2048), (904, 1024)]),
    (5000, True, [(5000, 8192)]),
    (100, True, [(100, 128)]),
], ids=["one-line", "small", "exact", "split-small-rest", "split-even",
        "split", "lone", "lone-small"])
def test_residual_chunks_keep_to_warm_rungs(u, lone, chunks):
    from log_parser_tpu.runtime.batcher import FLUSH_CHUNK_ROWS

    batcher = _tiny_batcher()
    try:
        assert batcher._residual_chunks(u, lone) == chunks
        assert sum(n for n, _ in chunks) == u
        if not lone:
            assert all(p <= FLUSH_CHUNK_ROWS for _, p in chunks)
    finally:
        batcher.close()


def test_a_large_flush_after_small_lone_requests_compiles_nothing():
    """The first flush at a width is a lone 20-line request; then eight
    1,500-line requests arrive together. Their 12,000-line residual runs
    as six dispatches on the ladder the first flush compiled, not as
    hundreds at the small request's rung, and compiles nothing."""
    from log_parser_tpu.models import PodFailureData
    from log_parser_tpu.runtime.batcher import FLUSH_CHUNK_ROWS

    batcher = _tiny_batcher(wait_ms=WAIT_MS)
    engine = batcher.engine
    engine.enable_line_cache(16)
    try:
        def logs(tag, n):
            return "\n".join(f"{tag} line {i} ok" for i in range(n))

        engine.analyze_batched(PodFailureData(pod={}, logs=logs("warm", 20)))
        compiled = engine.fused._jit_cube_plain._cache_size()
        shapes = []
        run_cube = engine._run_cube

        def recorded(lines_u8, lengths, n_rows, trace=None):
            shapes.append(lines_u8.shape)
            return run_cube(lines_u8, lengths, n_rows, trace=trace)

        engine._run_cube = recorded
        flushed = batcher.batches_flushed
        with ThreadPoolExecutor(max_workers=8) as pool:
            list(pool.map(
                lambda r: engine.analyze_batched(
                    PodFailureData(pod={}, logs=logs(f"r{r}", 1500))),
                range(8)))
        assert batcher.batches_flushed - flushed < 8  # they coalesced
        assert 1 < len(shapes) <= 8 * 1500 // FLUSH_CHUNK_ROWS + 8
        assert all(rows <= FLUSH_CHUNK_ROWS for rows, _ in shapes)
        assert engine.fused._jit_cube_plain._cache_size() == compiled
    finally:
        batcher.close()


# ------------------------------------------------- the cells' traffic

@pytest.mark.parametrize("fmt", [m["unique"] for m in _spec("hit_dense")["mix"][1:]])
def test_each_hit_dense_failure_format_matches_a_primary(fmt):
    from benchmark import reference as ref

    lib = ref.Library(BUILTIN83)
    line = fmt.format(m=7, s=59, tag="3f", i=12)
    assert line != fmt.format(m=7, s=59, tag="3f", i=13)
    matched = {lib.primaries[pi]["id"] for _, pi in ref.match_plain(lib, [line])}
    assert matched, line


@pytest.mark.parametrize("rate,seconds", [(0.8, 2.0), (24.0, 3.0), (190.0, 51.0)])
def test_flush_ticks_plan_gives_every_seed_the_same_work(rate, seconds):
    from benchmark.traffic import Traffic, plugin

    spec = _spec("tail_flush")
    loop = dict(spec["loop"], rate_per_s=rate)
    plan = plugin("arrivals", "flush_ticks").plan
    sizes = Traffic(spec, {}, 1).quantile_sizes
    runs = [plan(loop, sizes, seed, seconds) for seed in (1, 4000000007, 2**31 + 5)]
    n = max(1, round(rate * seconds))
    for due, size in runs:
        assert len(due) == len(size) == n
        assert due == sorted(due)
        assert 0 <= due[0] and due[-1] < seconds
        assert sorted(size) == sorted(runs[0][1])
        # each tick is one forwarder's flush: at most F distinct phases
        phases = {round(d % loop["flush_s"], 9) for d in due}
        assert len(phases) <= loop["forwarders"]
    if n > 50:
        # bursts: requests due together
        assert len(set(runs[0][0])) < n
        mean = rate * loop["flush_s"] / loop["forwarders"]
        assert abs(n / len(set(runs[0][0])) - mean) < 3 * math.sqrt(mean)
    assert runs[0] != runs[1] or n == 1
    np.testing.assert_equal(plugin("arrivals", "flush_ticks").poisson_quantiles(
        2.0, [0.1, 0.5, 0.99]), [0, 2, 6])
