"""Exact-match line cache (runtime/linecache.py + the engine/batcher
routing tier).

The contract under test: caching per-line device bit rows changes
THROUGHPUT, never semantics. Cache-on output — events, scores, frequency
snapshots — is identical to cache-off on the same stream, batched and
unbatched; a reload-epoch bump makes a stale hit structurally impossible;
an open per-pattern breaker overrides cached bits exactly like fresh
ones (per-pattern invalidation by construction); and a request served
wholly from cache never reaches the device step, so it can neither
strike quarantine nor trip the watchdog.
"""

from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.models.pod import PodFailureData
from log_parser_tpu.native.ingest import Corpus, normalize_blob
from log_parser_tpu.runtime import AnalysisEngine, faults
from log_parser_tpu.runtime.faults import FaultRegistry
import log_parser_tpu.runtime.linecache as lc
from log_parser_tpu.runtime.linecache import (
    LineCache,
    dedup_slots,
    line_keys,
    word_class,
)
from log_parser_tpu.runtime.quarantine import QuarantineTable

from conftest import FakeClock
from helpers import make_pattern, make_pattern_set


@pytest.fixture(autouse=True)
def clean_registry():
    faults.install(None)
    yield
    faults.install(None)


def _sets():
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
                make_pattern("conn", regex="Connection refused", confidence=0.7),
                make_pattern("fatal", regex="FATAL", confidence=0.8),
            ]
        )
    ]


def _pod(logs: str) -> PodFailureData:
    return PodFailureData(pod={"metadata": {"name": "lc"}}, logs=logs)


# repeat-heavy stream over a small template set, including lines that
# exercise every factor: secondary proximity, sequence chain, context
REPEAT_TEMPLATES = [
    "INFO steady-state heartbeat",
    "Full GC pause",
    "GC overhead limit reached",
    "java.lang.OutOfMemoryError: heap",
    "dial tcp 10.0.0.1: Connection refused",
    "FATAL disk controller",
]


def _stream(n_requests: int = 6, lines_per: int = 12) -> list[PodFailureData]:
    out = []
    for r in range(n_requests):
        lines = [
            REPEAT_TEMPLATES[(r * 7 + i * 3) % len(REPEAT_TEMPLATES)]
            for i in range(lines_per)
        ]
        # every third request carries one novel line (cache miss traffic)
        if r % 3 == 0:
            lines.append(f"WARN novel line {r}")
        out.append(_pod("\n".join(lines)))
    return out


def _events(result):
    return [
        (e.line_number, e.matched_pattern.id, e.score) for e in result.events
    ]


def _ctx(result):
    return [e.context for e in result.events]


def _freq_counts(engine) -> dict:
    return {k: len(v) for k, v in engine.frequency._save_state().items()}


def _cached_engine(mb: float = 4.0) -> AnalysisEngine:
    engine = AnalysisEngine(_sets(), ScoringConfig())
    engine.enable_line_cache(mb)
    return engine


# ------------------------------------------------------------ LRU mechanics


def _rows(cache: LineCache, lines: list[bytes]) -> list[np.ndarray | None]:
    """Each line's cached bool row, or None for a miss."""
    found = cache.lookup(line_keys(lines))
    unpacked = cache.unpack(found.packed)
    return [None if r < 0 else unpacked[r] for r in found.row.tolist()]


class TestLineCacheUnit:
    def test_lookup_populate_and_counters(self):
        cache = LineCache(n_columns=10, budget_bytes=1 << 20)
        assert _rows(cache, [b"alpha", b"beta", b"alpha"]) == [None] * 3
        assert cache.stats()["misses"] == 3

        row = np.zeros(10, dtype=bool)
        row[3] = True
        cache.populate(line_keys([b"alpha"]), row[None, :])
        got = _rows(cache, [b"alpha", b"beta"])
        assert got[1] is None
        np.testing.assert_array_equal(got[0], row)
        s = cache.stats()
        assert s["hits"] == 1 and s["misses"] == 4 and s["entries"] == 1

    def test_lru_eviction_bounded_by_resident_bytes(self):
        cache = LineCache(n_columns=64, budget_bytes=2000)
        lines = [b"line-%d" % i for i in range(100)]
        for j in range(0, 100, 10):  # ten calls, oldest first
            cache.populate(
                line_keys(lines[j : j + 10]), np.zeros((10, 64), dtype=bool)
            )
        s = cache.stats()
        assert s["evictions"] > 0
        assert s["residentBytes"] <= 2000
        assert s["entries"] < 100
        # the survivors are the most recently stored
        assert _rows(cache, [lines[-1]])[0] is not None
        assert _rows(cache, [lines[0]])[0] is None

    def test_flush_clears_and_rebinds_columns(self):
        cache = LineCache(n_columns=16, budget_bytes=1 << 20)
        cache.populate(line_keys([b"x"]), np.ones((1, 16), dtype=bool))
        cache.flush(n_columns=24)
        s = cache.stats()
        assert s["entries"] == 0
        assert s["residentBytes"] == 0
        assert s["epochFlushes"] == 1
        assert cache.n_columns == 24
        assert _rows(cache, [b"x"]) == [None]


# ----------------------------------------------------------- exact parity


class TestParity:
    def test_unbatched_stream_parity(self):
        """The same request stream through a cache-off and a cache-on
        engine: identical events, contexts, scores (exact), and frequency
        snapshot counts — including requests served entirely from cache."""
        stream = _stream()
        off = AnalysisEngine(_sets(), ScoringConfig())
        on = _cached_engine()
        for data in stream:
            r_off = off.analyze_pipelined(data)
            r_on = on.analyze_pipelined(data)
            assert _events(r_off) == _events(r_on)
            assert _ctx(r_off) == _ctx(r_on)
        assert _freq_counts(off) == _freq_counts(on)
        s = on.line_cache.stats()
        assert s["hits"] > 0 and s["residualRows"] > 0
        assert on.fallback_count == 0

    def test_all_hit_request_skips_device_entirely(self):
        engine = _cached_engine()
        data = _pod("\n".join(REPEAT_TEMPLATES))
        engine.analyze_pipelined(data)
        before = engine.line_cache.stats()
        engine.analyze_pipelined(data)
        after = engine.line_cache.stats()
        assert after["residualRows"] == before["residualRows"]
        assert after["hits"] == before["hits"] + len(REPEAT_TEMPLATES)
        # no device phase in the trace: the request never dispatched
        assert "device" not in engine.last_trace.as_dict()

    def test_in_request_dedup_one_device_row_per_unique_line(self):
        engine = _cached_engine()
        logs = "\n".join(["java.lang.OutOfMemoryError: heap"] * 9 + ["INFO x"] * 3)
        engine.analyze_pipelined(_pod(logs))
        s = engine.line_cache.stats()
        assert s["residualRows"] == 2  # 12 lines, 2 unique
        assert s["dedupFanout"] == 10

    def test_needs_host_lines_cached_request_parity(self):
        """Non-ASCII lines (python-fallback encode → needs_host) ride the
        override splice: parity holds and they are never populated — a
        repeat still pays a residual row for them."""
        logs = (
            "INFO café latte ☃\n"
            "java.lang.OutOfMemoryError: heap\n"
            "INFO café latte ☃"
        )
        off = AnalysisEngine(_sets(), ScoringConfig())
        on = _cached_engine()
        assert _events(off.analyze_pipelined(_pod(logs))) == _events(
            on.analyze_pipelined(_pod(logs))
        )
        first = on.line_cache.stats()["residualRows"]
        assert _events(off.analyze_pipelined(_pod(logs))) == _events(
            on.analyze_pipelined(_pod(logs))
        )
        # the ASCII line is a hit; the non-ASCII line misses again
        assert on.line_cache.stats()["residualRows"] > first

    def test_empty_and_trivial_logs(self):
        off = AnalysisEngine(_sets(), ScoringConfig())
        on = _cached_engine()
        # a lone surrogate takes the scalar encode, and keys like any line
        surrogate = "FATAL \ud800 x\nINFO only\nFATAL \ud800 x\nFATAL ? x"
        for logs in ("", "\n", "INFO only", surrogate, surrogate):
            assert _events(off.analyze_pipelined(_pod(logs))) == _events(
                on.analyze_pipelined(_pod(logs))
            )

    def test_batched_stream_parity(self):
        """Full-batch flushes through the cached path == the same stream
        served serially by a cache-off engine — exact equality, with the
        cross-flush dedup visible in the counters."""
        stream = _stream(n_requests=4, lines_per=8)
        serial = AnalysisEngine(_sets(), ScoringConfig())
        expected = [_events(serial.analyze_pipelined(d)) for d in stream]

        engine = _cached_engine()
        engine.enable_batching(wait_ms=5000.0, batch_max=len(stream))
        try:
            pend = [engine.batcher._enqueue(d, None) for d in stream]
            for p in pend:
                assert p.done.wait(60.0)
            for p, want in zip(pend, expected):
                assert p.error is None
                assert _events(p.result) == want
            assert _freq_counts(serial) == _freq_counts(engine)
            s = engine.line_cache.stats()
            # cross-flush dedup: way fewer device rows than total lines
            assert 0 < s["residualRows"] <= len(REPEAT_TEMPLATES) + 4
            assert s["dedupFanout"] > 0
            assert engine.fallback_count == 0
        finally:
            engine.batcher.close()

    def test_batched_all_hit_flush_zero_device_rows(self):
        engine = _cached_engine()
        engine.enable_batching(wait_ms=5000.0, batch_max=2)
        data = _pod("\n".join(REPEAT_TEMPLATES[:4]))
        try:
            engine.analyze_batched(data)  # populates (single-item flush)
            base = engine.line_cache.stats()["residualRows"]
            pend = [engine.batcher._enqueue(data, None) for _ in range(2)]
            for p in pend:
                assert p.done.wait(60.0)
                assert p.error is None
            assert engine.line_cache.stats()["residualRows"] == base
        finally:
            engine.batcher.close()


# ----------------------------------------------------- epoch invalidation


class TestInvalidation:
    def test_reload_epoch_flush_makes_stale_hit_impossible(self):
        """Swap the library under a warm cache: the new bank's results
        must be what a cold cache-off engine produces — no bit row from
        the old library may survive the swap."""
        engine = _cached_engine()
        logs = "INFO boot\njava.lang.OutOfMemoryError: heap\nNo space left on device"
        engine.analyze_pipelined(_pod(logs))  # warm: oom matches
        assert engine.line_cache.stats()["entries"] > 0

        v2 = [
            make_pattern_set(
                [
                    # same id, CHANGED regex: a stale cached row would
                    # keep matching the old semantics
                    make_pattern("oom", regex="No space left on device",
                                 confidence=0.9, severity="CRITICAL"),
                ],
                "lib-v2",
            )
        ]
        source = AnalysisEngine(v2, ScoringConfig())
        engine.apply_library(source)
        s = engine.line_cache.stats()
        assert s["epochFlushes"] == 1
        assert s["entries"] == 0

        fresh = AnalysisEngine(v2, ScoringConfig())
        r_on = engine.analyze_pipelined(_pod(logs))
        r_off = fresh.analyze_pipelined(_pod(logs))
        assert _events(r_on) == _events(r_off)
        # the old regex must NOT fire: line 3 matches, line 2 does not
        assert [e[0] for e in _events(r_on)] == [3]

    def test_breaker_trip_overrides_cached_bits_per_pattern(self):
        """Per-pattern invalidation by construction: an OPEN breaker's
        columns are re-evaluated from the host regex over cached rows
        too. Corrupt one pattern's cached bit and trip its breaker — the
        corruption is contained the moment the breaker opens, while the
        OTHER patterns' cached bits keep serving."""
        engine = _cached_engine()
        logs = "java.lang.OutOfMemoryError: heap\ndial tcp: Connection refused"
        want = _events(engine.analyze_pipelined(_pod(logs)))
        assert [e[1] for e in want] == ["oom", "conn"]

        # simulate a divergent device result resident in the cache:
        # clear the oom primary bit of the cached OOM line
        cache = engine.line_cache
        keys = line_keys([b"java.lang.OutOfMemoryError: heap"])
        oom_pat = [p.id for p in engine.bank.patterns].index("oom")
        oom_col = int(engine.bank.primary_columns[oom_pat])
        with cache.lock:
            table = cache._tables[int(word_class(keys.lengths)[0])]
            (eid,), *_ = table.find(keys, np.arange(1))
            row = np.unpackbits(table.packed[eid], count=cache.n_columns)
            row[oom_col] = 0
            table.packed[eid] = np.packbits(row)

        # corrupted bits ARE served (proves the hit path is live)
        broken = _events(engine.analyze_pipelined(_pod(logs)))
        assert [e[1] for e in broken] == ["conn"]

        # breaker trip: oom's columns now come from the exact host regex
        # on every request — cached rows included
        engine.breakers.trip("oom")
        healed = _events(engine.analyze_pipelined(_pod(logs)))
        assert [(ln, pid) for ln, pid, _ in healed] == [
            (ln, pid) for ln, pid, _ in want
        ]
        # conn kept serving from cache throughout
        assert engine.line_cache.stats()["hits"] > 0


# ------------------------------------------------- quarantine interaction


class TestQuarantine:
    def _engine(self):
        engine = _cached_engine()
        engine.fallback_to_golden = True
        engine.quarantine = QuarantineTable(
            strikes=1, ttl_s=600.0, clock=FakeClock()
        )
        return engine

    def test_cache_hits_never_strike(self):
        """Arm a keyed poison fault AFTER the cache is warm: the repeat
        request is served entirely from cache, never reaches the device
        step, and the fault's fired counter pins that. A novel request
        sharing the key DOES pay a residual and strikes."""
        engine = self._engine()
        logs = "INFO boot\njava.lang.OutOfMemoryError: heap"
        want = _events(engine.analyze_pipelined(_pod(logs)))  # warm, healthy

        reg = FaultRegistry.parse("quarantine_raise@match=INFO boot")
        faults.install(reg)
        repeat = engine.analyze_pipelined(_pod(logs))
        assert _events(repeat) == want
        assert reg.specs[0].fired == 0  # device step never entered
        assert engine.fallback_count == 0
        assert engine.quarantine.stats()["strikes"] == 0

        # novel content with the same fault key: residual dispatch fires
        novel = engine.analyze_pipelined(_pod(logs + "\nWARN never seen"))
        assert novel.events  # served from golden fallback
        assert reg.specs[0].fired == 1
        assert engine.fallback_count == 1
        assert engine.quarantine.stats()["strikes"] == 1

    def test_batched_cached_flush_poison_falls_back_to_bisection(self):
        """A poisoned residual in a cached flush retries wholesale on the
        uncached path, where bisection isolates the poison row — healthy
        batchmates stay on-device, only the culprit strikes."""
        engine = self._engine()
        engine.enable_batching(wait_ms=5000.0, batch_max=2)
        poison = _pod("POISON-PILL marker\nINFO filler")
        healthy = _pod("dial tcp: Connection refused\nINFO filler")
        faults.install(FaultRegistry.parse("quarantine_raise@match=POISON-PILL"))
        try:
            pend = [
                engine.batcher._enqueue(d, None) for d in (poison, healthy)
            ]
            for p in pend:
                assert p.done.wait(60.0)
            assert pend[0].error is None and pend[1].error is None
            assert [e[1] for e in _events(pend[1].result)] == ["conn"]
            assert engine.fallback_count == 1  # poison only
            assert engine.quarantine.stats()["quarantined"] == 1
            assert engine.batcher.stats()["bisects"] >= 1
        finally:
            engine.batcher.close()


# ------------------------------------------------------- one hash path


class TestKeyStability:
    def test_key_material_is_the_ingest_normalized_blob(self):
        """``line_key_bytes`` slices the SAME normalization the quarantine
        fingerprint hashes (normalize_blob) — no second normalization
        pass, surrogates and all."""
        logs = "plain ascii\ncafé ☃\nbad \ud800 surrogate"
        corpus = Corpus(logs)
        joined = b"\n".join(
            corpus.line_key_bytes(i) for i in range(corpus.n_lines)
        )
        assert joined == normalize_blob(logs)

    def test_key_stable_across_http_framed_grpc_ingest(self):
        """One payload through all three transport codecs: HTTP JSON,
        the framed shim's protobuf Envelope, and the gRPC ParseRequest —
        every decode yields byte-identical per-line cache keys."""
        from log_parser_tpu.shim import logparser_pb2 as pb

        logs = "INFO café\njava.lang.OutOfMemoryError: heap\n☃ snow"

        # HTTP: JSON body round-trip (serve/http.py reads payload["logs"])
        http_logs = json.loads(json.dumps({"logs": logs}))["logs"]
        # gRPC: ParseRequest proto round-trip
        grpc_logs = pb.ParseRequest.FromString(
            pb.ParseRequest(logs=logs).SerializeToString()
        ).logs
        # framed shim: Envelope-wrapped ParseRequest round-trip
        env = pb.Envelope(
            method="Parse",
            payload=pb.ParseRequest(logs=logs).SerializeToString(),
        )
        framed_logs = pb.ParseRequest.FromString(
            pb.Envelope.FromString(env.SerializeToString()).payload
        ).logs

        keys = []
        for decoded in (http_logs, grpc_logs, framed_logs):
            _, _, k, _ = dedup_slots(Corpus(decoded))
            keys.append(
                (k.words[k.rows].tolist(), k.lengths.tolist(),
                 k.probes.tolist(), k.storable.tolist())
            )
        assert keys[0] == keys[1] == keys[2]

    def test_python_fallback_keys_match_native_blob_slices(self, monkeypatch):
        """The python-fallback encode produces the same key bytes as the
        native blob slices, so a warm cache survives either ingest path."""
        import log_parser_tpu.native.ingest as ingest_mod

        logs = "INFO a\njava.lang.OutOfMemoryError: heap\nINFO b"
        native_corpus = Corpus(logs)
        monkeypatch.setattr(ingest_mod, "get_lib", lambda: None)
        fallback_corpus = Corpus(logs)
        # the vectorized fallback is blob-backed like the native path;
        # only the lone-surrogate scalar path keeps materialized strings
        assert fallback_corpus._blob is not None
        for i in range(native_corpus.n_lines):
            assert native_corpus.line_key_bytes(i) == fallback_corpus.line_key_bytes(i)
        # surrogate corpora take the scalar path and still agree per line
        scalar_corpus = Corpus("INFO a\n\ud800INFO b")
        assert scalar_corpus._lines is not None
        assert scalar_corpus.line_key_bytes(0) == b"INFO a"


# ----------------------------------------------------------- concurrency


def test_concurrent_cached_requests_thread_safe():
    """Pipelined requests sharing one cache race lookups against
    populates; results must stay per-request correct."""
    engine = _cached_engine()
    stream = _stream(n_requests=8, lines_per=6)
    serial = AnalysisEngine(_sets(), ScoringConfig())
    expected = [_events(serial.analyze_pipelined(d)) for d in stream]

    results: list = [None] * len(stream)

    def worker(j):
        results[j] = _events(engine.analyze_pipelined(stream[j]))

    threads = [
        threading.Thread(target=worker, args=(j,)) for j in range(len(stream))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # per-request events and scores are frequency-independent here only
    # for line/pattern identity; frequency-coupled scores may differ by
    # arrival order, so compare line/pattern sets per request
    for got, want in zip(results, expected):
        assert [(ln, pid) for ln, pid, _ in got] == [
            (ln, pid) for ln, pid, _ in want
        ]
    assert _freq_counts(engine) == _freq_counts(serial)


# ------------------------------------------------ content-addressed table


def _bits_of(line: bytes, n_columns: int = 24) -> np.ndarray:
    """A stand-in device row: a pure function of the line's bytes."""
    rng = np.random.default_rng(list(line) + [len(line)])
    return rng.random(n_columns) < 0.3


def _bytes(k: int, cap: int) -> int:
    """A ``k``-word table's bytes at ``cap`` slots of a 24-column bank,
    with the least index that holds them, as the table counts them."""
    return lc._Table(k, 3).bytes_at(cap, tight=True)


def _array_bytes(cache: LineCache) -> int:
    """The bytes every table's arrays really hold, the index's included."""
    return sum(
        a.nbytes
        for t in cache._tables.values()
        for a in (t.words, t.lengths, t.packed, t.stamp, t.probe, t.bucket,
                  t.index)
    )


def _dict_loop(corpus):
    """The scalar reference: slots by first appearance, keyed by bytes."""
    slot_of: dict[bytes, int] = {}
    line_slot, reps = [], []
    for i in range(corpus.n_lines):
        lb = corpus.line_key_bytes(i)
        if lb not in slot_of:
            slot_of[lb] = len(reps)
            reps.append(i)
        line_slot.append(slot_of[lb])
    return line_slot, reps


class TestContentKeys:
    """The table keyed by content words against a plain dict keyed by the
    content bytes: same hits, same rows, through collisions, widths and
    eviction."""

    def _serve(self, cache, ref, lines, n_columns=24):
        """One request through dedup → lookup → populate, checked against
        ``ref`` (bytes → row of every line stored so far)."""
        corpus = Corpus("\n".join(lines))
        line_slot, reps, keys, counts = dedup_slots(corpus)
        found = cache.lookup(keys, counts)
        rows = cache.unpack(found.packed)
        for s, r in enumerate(found.row.tolist()):
            lb = corpus.line_key_bytes(int(reps[s]))
            if r >= 0:
                assert keys.storable[s]
                np.testing.assert_array_equal(rows[r], ref[lb])
        miss = np.flatnonzero(found.row < 0)
        fresh = np.stack(
            [_bits_of(corpus.line_key_bytes(int(reps[s])), n_columns)
             for s in miss]
        ) if miss.size else np.zeros((0, n_columns), dtype=bool)
        cache.populate(keys.take(miss), fresh)
        for j, s in enumerate(miss.tolist()):
            if keys.storable[s]:
                ref[corpus.line_key_bytes(int(reps[s]))] = fresh[j]
        return found, keys

    def test_cold_and_warm_parity_with_bytes_dict(self):
        lines = [
            REPEAT_TEMPLATES[(i * 5) % len(REPEAT_TEMPLATES)]
            for i in range(200)
        ] + [f"novel line {i}" for i in range(40)]
        cache, ref = LineCache(24, 1 << 20), {}
        cold, _ = self._serve(cache, ref, lines)
        assert (cold.row < 0).all()
        warm, keys = self._serve(cache, ref, lines)
        assert (warm.row >= 0).all()
        assert cache.stats()["entries"] == len(ref) == keys.rows.size
        # another corpus shape (a wider batch) hits the same entries
        wide, _ = self._serve(cache, ref, lines + ["x" * 200])
        assert (wide.row >= 0).sum() == keys.rows.size
        assert cache.stats()["probeCollisions"] == 0

    def test_forced_probe_collision_stays_exact_and_counted(self, monkeypatch):
        monkeypatch.setattr(
            lc, "probe64", lambda words, lengths: np.full(
                words.shape[0], 7, dtype=np.uint64)
        )
        lines = ["alpha", "beta", "alpha", "gamma", "beta", "alphb"]
        corpus = Corpus("\n".join(lines))
        line_slot, reps, keys, counts = dedup_slots(corpus)
        ref_slot, ref_reps = _dict_loop(corpus)
        assert line_slot.tolist() == ref_slot
        assert reps.tolist() == ref_reps
        cache, ref = LineCache(24, 1 << 20), {}
        self._serve(cache, ref, lines)
        # one entry a probe: the first line is stored, the rest collide
        assert cache.stats()["entries"] == 1
        assert cache.stats()["probeCollisions"] == 3
        found, _ = self._serve(cache, ref, lines)
        assert (found.row >= 0).sum() == 1
        assert cache.stats()["probeCollisions"] > 3

    def test_same_line_hits_at_widths_64_and_128(self):
        line = "java.lang.OutOfMemoryError: heap"
        narrow = Corpus("\n".join([line, "y" * 60]))
        wide = Corpus("\n".join([line, "y" * 120]))
        assert narrow.encoded.u8.shape[1] == 64
        assert wide.encoded.u8.shape[1] == 128
        cache = LineCache(24, 1 << 20)
        _, reps, keys, counts = dedup_slots(narrow)
        cache.populate(keys, np.stack([_bits_of(b"%d" % i) for i in range(2)]))
        _, _, wkeys, wcounts = dedup_slots(wide)
        assert wkeys.probes[0] == keys.probes[0]
        found = cache.lookup(wkeys, wcounts)
        assert found.row.tolist() == [0, -1]
        np.testing.assert_array_equal(
            cache.unpack(found.packed)[0], _bits_of(b"0")
        )
        # and a key built from the bytes alone (the stream's) hits too
        assert cache.lookup(line_keys([line.encode()])).row.tolist() == [0]

    def test_over_long_and_needs_host_lines_never_stored(self):
        shorts = [f"short {i:04d}" for i in range(600)]
        prefix = "P" * 120
        a, b = prefix + "A" * 40, prefix + "B" * 40  # differ past the width
        lines = shorts + [a, b, "café ☃", a]
        corpus = Corpus("\n".join(lines))
        assert corpus.encoded.u8.shape[1] < len(a)
        line_slot, reps, keys, counts = dedup_slots(corpus)
        assert line_slot.tolist() == _dict_loop(corpus)[0]
        tail = keys.take(np.arange(600, 603))
        assert not tail.storable.any()
        cache = LineCache(24, 1 << 20)
        cache.populate(tail, np.ones((3, 24), dtype=bool))
        assert cache.stats()["entries"] == 0
        found = cache.lookup(tail, counts[600:603])
        assert (found.row < 0).all()
        assert cache.stats()["misses"] == 4  # line-weighted: a twice

    def test_eviction_keeps_parity_and_the_current_call(self):
        # a budget of 100 entries against 60 unique lines a call
        cache, ref = LineCache(24, _bytes(2, 100)), {}
        for r in range(4):
            lines = [f"round {r} line {i}" for i in range(60)]
            found, keys = self._serve(cache, ref, lines + lines[:30])
            # every row this call stored is still there
            again = cache.lookup(keys)
            assert (again.row >= 0).all()
            s = cache.stats()
            assert s["residentBytes"] <= cache.budget_bytes
        assert cache.stats()["evictions"] > 0
        # an old round's lines miss, and nothing served was ever wrong
        old, _ = self._serve(cache, ref, [f"round 0 line {i}" for i in range(60)])
        assert (old.row < 0).any()

    def test_eviction_never_drops_what_the_call_touched(self):
        # room for four entries; the second call touches L1 and brings
        # four new lines: L2-L4 go, L1 stays, and three new lines fit
        cache = LineCache(24, _bytes(2, 4))
        old = [b"line %d" % i for i in range(1, 5)]
        cache.populate(line_keys(old), np.stack([_bits_of(b) for b in old]))
        new = [b"new %d" % i for i in range(4)]
        batch = old[:1] + new
        cache.populate(line_keys(batch), np.stack([_bits_of(b) for b in batch]))
        assert _rows(cache, old)[1:] == [None] * 3
        np.testing.assert_array_equal(_rows(cache, old[:1])[0], _bits_of(old[0]))
        s = cache.stats()
        assert s["entries"] == 4 and s["residentBytes"] <= _bytes(2, 4)

    def test_eviction_cost_follows_entries_not_calls(self):
        # a server that ran ~1e9 calls before its first eviction: evicting
        # (on a populate, then on a budget shrink) allocates by the
        # entries, not by the calls since the oldest stamp
        import tracemalloc

        cache = LineCache(24, _bytes(2, 50))
        old = [b"old line %d" % i for i in range(40)]
        new = [b"new line %d" % i for i in range(30)]

        def store(lines):
            cache.populate(line_keys(lines), np.stack([_bits_of(b) for b in lines]))

        store(old)
        tracemalloc.start()
        try:
            cache._gen = 10**9
            store(new)
            cache._gen = 2 * 10**9
            cache.set_budget(_bytes(2, 30))
            peak = tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()
        assert peak < 1 << 20
        assert cache.stats()["residentBytes"] <= _bytes(2, 30)
        for line, row in zip(new, _rows(cache, new)):
            np.testing.assert_array_equal(row, _bits_of(line))
        assert all(r is None for r in _rows(cache, old))

    def test_upkeep_follows_the_call_not_the_resident_entries(self):
        # 200k resident entries: a 100-key populate or lookup allocates
        # under 1 MB, and an eviction beyond its partition of the stamps
        # (two arrays of the resident entries) by the entries it evicts:
        # nothing merges, filters or rebuilds an index of every entry
        import tracemalloc

        n = 200_000
        cache = LineCache(24, _bytes(2, n))
        fill = [b"fill %06d" % i for i in range(n)]
        cache.populate(line_keys(fill), np.zeros((n, 24), dtype=bool))
        assert cache.stats()["entries"] == n

        def traced(fn):
            tracemalloc.start()
            try:
                fn()
                return tracemalloc.get_traced_memory()[1]
            finally:
                tracemalloc.stop()

        def call(tag):
            lines = [b"%s %06d" % (tag, i) for i in range(100)]
            return lines, line_keys(lines), np.stack([_bits_of(b) for b in lines])

        # the first call past the budget evicts a sixteenth of it
        _, keys, rows = call(b"next")
        peak = traced(lambda: cache.populate(keys, rows))
        evicted = cache.stats()["evictions"]
        assert 0 < evicted < n // 8
        assert peak - 2 * 8 * n < 1 << 20
        # the next calls store into the slots it freed
        lines, keys, rows = call(b"more")
        assert traced(lambda: cache.populate(keys, rows)) < 1 << 20
        assert traced(lambda: cache.lookup(keys)) < 1 << 20
        assert cache.stats()["evictions"] == evicted
        for line, row in zip(lines, _rows(cache, lines)):
            np.testing.assert_array_equal(row, _bits_of(line))

    @pytest.mark.parametrize("one_run", [False, True], ids=["hashed", "one_run"])
    def test_churn_keeps_the_index_equal_to_a_dict(self, monkeypatch, one_run):
        """One table churned through inserts, evictions, re-inserts of
        evicted lines, compactions (budget shrinks) and rebuilds: after
        each call every line the table holds hits with the dict model's
        row, every other line misses, and no probe collides. ``one_run``
        keeps each probe's low half only, so every key's home is bucket
        0 and the index is one linear run."""
        import random

        if one_run:
            real = lc.probe64
            monkeypatch.setattr(
                lc, "probe64",
                lambda words, lengths: real(words, lengths) >> np.uint64(32))
        rebuilds = []
        rebuild = lc._Table._rebuild

        def counted(table, size):
            rebuilds.append((table.index.size, size))
            rebuild(table, size)

        monkeypatch.setattr(lc._Table, "_rebuild", counted)
        rng = random.Random(28)
        pool = [b"churn line %05d" % i for i in range(600 if one_run else 3000)]
        model = {b: _bits_of(b) for b in pool}
        pool_keys = line_keys(pool)
        cache = LineCache(24, _bytes(2, 400))
        stored, reinserted, epochs = set(), 0, set()
        for c in range(40 if one_run else 120):
            if c % 30 == 29:  # a shrink compacts; the next calls grow back
                cache.set_budget(_bytes(2, rng.randrange(60, 200)))
            elif c % 30 == 0:
                cache.set_budget(_bytes(2, 400))
            lines = rng.sample(pool, rng.randrange(1, 60 if one_run else 300))
            keys = line_keys(lines)
            found = cache.lookup(keys)
            got = cache.unpack(found.packed)
            for j, r in enumerate(found.row.tolist()):
                if r >= 0:
                    np.testing.assert_array_equal(got[r], model[lines[j]])
            miss = np.flatnonzero(found.row < 0)
            reinserted += sum(lines[j] in stored for j in miss)
            stored.update(lines[j] for j in miss)
            cache.populate(
                keys.take(miss),
                np.stack([model[lines[j]] for j in miss]) if miss.size
                else np.zeros((0, 24), dtype=bool),
                found,
            )
            table = cache._tables[2]
            epochs.add(table.epoch)
            live = np.flatnonzero(table.stamp[: table.n] != lc._FREE)
            held = {table.words[e].view(np.uint8)[: table.lengths[e]].tobytes()
                    for e in live.tolist()}
            eid = table.find(pool_keys, np.arange(len(pool)))[0]
            assert (eid >= 0).tolist() == [b in held for b in pool]
            rows = cache.unpack(table.packed[eid[eid >= 0]])
            want = [model[b] for b in pool if b in held]
            np.testing.assert_array_equal(rows, np.array(want).reshape(rows.shape))
            s = cache.stats()
            assert s["entries"] == len(held)
            assert s["residentBytes"] == _array_bytes(cache) <= cache.budget_bytes
        s = cache.stats()
        assert s["probeCollisions"] == 0 and s["evictions"] > 0
        assert reinserted > 0 and len(epochs) > 1
        # the index grew and shrank; hashed, it was cleared of deleted
        # buckets too (one run's inserts take them back at its head)
        assert sum(new > old for old, new in rebuilds) >= 2
        assert any(new < old for old, new in rebuilds)
        if one_run:
            assert s["indexProbes"] > 100 * len(pool)
        else:
            assert sum(new == old for old, new in rebuilds) >= 2

    def test_eviction_takes_the_oldest_entries_of_every_class(self):
        # calls of one- and two-word lines under a full budget: each call
        # evicts the oldest calls' lines, whatever their class, and every
        # table closed up or grown keeps its rows and its index
        cache = LineCache(24, _bytes(2, 200))
        calls = []
        for r in range(12):
            lines = [b"tick %03d %04d" % (r, i) for i in range(40 + 7 * (r % 3))]
            lines += [b"p%02d%03d" % (r, i) for i in range(5 + 9 * (r % 2))]
            cache.populate(line_keys(lines),
                           np.stack([_bits_of(b) for b in lines]))
            calls.append(lines)
            s = cache.stats()
            assert s["residentBytes"] <= cache.budget_bytes
            assert s["residentBytes"] == _array_bytes(cache)
        kept = [[r is not None for r in _rows(cache, lines)] for lines in calls]
        # whole calls survive from the newest back, the oldest not at all
        whole = [all(k) for k in kept]
        assert whole[-1] and not any(kept[0])
        assert whole == sorted(whole)
        assert sum(any(k) and not all(k) for k in kept) <= 1
        for lines in calls[-3:]:
            for line, row in zip(lines, _rows(cache, lines)):
                np.testing.assert_array_equal(row, _bits_of(line))

    def test_small_calls_share_one_eviction_of_a_sixteenth(self):
        budget = _bytes(2, 160)
        cache = LineCache(24, budget)

        def store(lines):
            cache.populate(line_keys(lines), np.stack([_bits_of(b) for b in lines]))

        for r in range(4):
            store([b"fill %d %03d" % (r, i) for i in range(40)])
        assert cache.stats()["evictions"] == 0
        assert cache.stats()["residentBytes"] == budget
        # two lines need 86 bytes; the first such call evicts a sixteenth
        # of the budget, 558 bytes (13 entries), and the next five calls
        # store into what it freed
        sixteenth = -(-(budget // 16) // lc._Table(2, 3).entry_bytes)
        assert sixteenth == 13
        store([b"small 0 %d" % i for i in range(2)])
        assert cache.stats()["evictions"] == sixteenth
        for r in range(1, 6):
            store([b"small %d %d" % (r, i) for i in range(2)])
        s = cache.stats()
        assert s["evictions"] == sixteenth and s["entries"] == 159
        gone = [sum(r is None for r in _rows(cache, [b"fill %d %03d" % (r, i)
                                                    for i in range(40)]))
                for r in range(4)]
        assert gone == [sixteenth, 0, 0, 0]  # of the oldest call

    def test_a_new_class_takes_the_other_classes_oldest_bytes(self):
        cache = LineCache(24, _bytes(2, 200))
        calls = [[b"tick %02d %04d" % (r, i) for i in range(n)]
                 for r, n in enumerate((100, 60, 40))]
        for lines in calls:
            cache.populate(line_keys(lines),
                           np.stack([_bits_of(b) for b in lines]))
        short = [b"s%d" % i for i in range(10)]  # the first one-word lines
        cache.populate(line_keys(short), np.stack([_bits_of(b) for b in short]))
        t1 = cache._tables[1]
        assert t1.live == t1.cap == 10
        assert cache.stats()["residentBytes"] <= cache.budget_bytes
        # the bytes came from the oldest call's lines, and from no other
        gone = [sum(r is None for r in _rows(cache, lines)) for lines in calls]
        assert gone[0] > 0 and gone[1:] == [0, 0]
        for line, row in zip(short, _rows(cache, short)):
            np.testing.assert_array_equal(row, _bits_of(line))

    def test_populate_touches_the_lines_its_request_hit(self):
        # room for ten entries. Request 2 hits F, then another request
        # stores five lines before request 2 stores its one miss: F is in
        # use until request 2 is done, so the next five rows evict the
        # other request's lines before F.
        cache = LineCache(24, _bytes(1, 10))

        def store(lines, found=None):
            cache.populate(line_keys(lines),
                           np.stack([_bits_of(b) for b in lines]), found)

        store([b"F", b"a1", b"a2", b"a3", b"a4"])
        found = cache.lookup(line_keys([b"F", b"r2"]))
        store([b"x%d" % i for i in range(5)])
        store([b"r2"], found)
        store([b"y%d" % i for i in range(5)])
        np.testing.assert_array_equal(_rows(cache, [b"F"])[0], _bits_of(b"F"))
        others = _rows(cache, [b"x%d" % i for i in range(5)])
        assert sum(r is None for r in others) == 2

    def test_slot_order_by_first_appearance_matches_dict_loop(self, monkeypatch):
        # a 2-value fold makes every run of equal probes hold different
        # lines: the exact regroup must still number slots as the dict does
        real = lc.probe64
        monkeypatch.setattr(
            lc, "probe64", lambda words, lengths: real(words, lengths)
            & np.uint64(1)
        )
        import random

        rng = random.Random(11)
        pool = [f"err {i}" for i in range(12)] + ["", "a" * 64, "a" * 65]
        for _ in range(40):
            lines = [rng.choice(pool) for _ in range(rng.randrange(1, 50))]
            corpus = Corpus("\n".join(lines))
            line_slot, reps, keys, counts = dedup_slots(corpus)
            ref_slot, ref_reps = _dict_loop(corpus)
            assert line_slot.tolist() == ref_slot
            assert reps.tolist() == ref_reps
            assert counts.tolist() == np.bincount(ref_slot).tolist()

    def test_threads_sharing_one_table_never_serve_a_wrong_row(self):
        """Lookups, populates and evictions from more threads than cores
        under a short switch interval: every hit is the row computed for
        that line's bytes, and the budget holds."""
        import sys

        cache = LineCache(24, _bytes(4, 40))
        lines = [b"shared line %d" % i for i in range(30)]
        bad: list = []

        def worker(w):
            for r in range(30):
                batch = lines[(w + r) % 10 :][:12] + [b"own %d %d" % (w, r)]
                keys = line_keys(batch)
                found = cache.lookup(keys)
                rows = cache.unpack(found.packed)
                for j, at in enumerate(found.row.tolist()):
                    if at >= 0 and not (rows[at] == _bits_of(batch[j])).all():
                        bad.append(batch[j])
                miss = np.flatnonzero(found.row < 0)
                cache.populate(
                    keys.take(miss),
                    np.stack([_bits_of(batch[j]) for j in miss])
                    if miss.size else np.zeros((0, 24), dtype=bool),
                )

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=worker, args=(w,))
                       for w in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60.0)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(old)
        assert bad == []
        s = cache.stats()
        assert s["residentBytes"] <= cache.budget_bytes
        assert s["evictions"] > 0 and s["hits"] > 0

    def test_a_corrupt_index_raises_rather_than_spins(self):
        cache = LineCache(24, 1 << 20)
        cache.populate(line_keys([b"x"]), np.ones((1, 24), dtype=bool))
        cache._tables[1].index[:] = 0  # no empty bucket ends a walk
        with pytest.raises(RuntimeError):
            cache.lookup(line_keys([b"y"]))
        assert not cache.lock.locked()

    def test_cache_counters_are_exported_in_metrics(self):
        engine = _cached_engine()
        data = _pod("\n".join(REPEAT_TEMPLATES))
        engine.analyze_pipelined(data)
        engine.analyze_pipelined(data)
        s = engine.line_cache.stats()
        reg = engine.obs.registry
        for key, metric, _ in lc.CACHE_METRIC_SAMPLES:
            assert reg.collected_value(metric, tenant="default") == s[key]
        assert 'logparser_line_cache_index_probes_total{tenant="default"}' in (
            reg.render())

    def test_engine_cache_path_counts_no_collisions(self):
        engine = _cached_engine()
        data = _pod("\n".join(REPEAT_TEMPLATES))
        engine.analyze_pipelined(data)
        assert "cache.populate" in engine.last_trace.stage_dict()
        engine.analyze_pipelined(data)
        assert "cache.populate" not in engine.last_trace.stage_dict()
        s = engine.line_cache.stats()
        assert s["entries"] == len(REPEAT_TEMPLATES)
        assert s["hits"] == len(REPEAT_TEMPLATES)
        assert s["probeCollisions"] == 0
