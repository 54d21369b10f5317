"""Crash-safe on-disk caches: checksum sidecars, quarantine of corrupt
or truncated entries, rebuild-not-raise, and the ``cache`` fault site
(patterns/libcache.py sidecars + utils/xlacache.py integrity sweep)."""

from __future__ import annotations

import hashlib
import os

import pytest

from helpers import make_pattern, make_pattern_set

from log_parser_tpu.runtime import faults
from log_parser_tpu.runtime.faults import FaultRegistry


@pytest.fixture(autouse=True)
def clean_faults():
    faults.install(None)
    yield
    faults.install(None)


@pytest.fixture()
def cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("LOG_PARSER_TPU_CACHE", str(tmp_path))
    # these tests pin the DISK snapshot layer (quarantine, fault
    # injection, lazy restore); the in-process pack memo would answer
    # warm loads before the disk is ever read, so park it — the memo
    # has its own coverage (tests/test_fleet.py TestPackSharing)
    monkeypatch.setenv("LOG_PARSER_TPU_PACK_SHARE", "0")
    from log_parser_tpu.patterns import libcache
    libcache.reset_packs()
    return tmp_path


def _sets():
    return [
        make_pattern_set(
            [
                make_pattern("oom", regex="OutOfMemoryError", confidence=0.9),
                make_pattern("to", regex="\\btimeout\\b", confidence=0.7,
                             severity="MEDIUM"),
            ]
        )
    ]


def _snapshot(cache_dir):
    (path,) = (cache_dir / "bank").glob("*.pkl")
    return path


# ----------------------------------------------------------- libcache


class TestLibcacheCrashSafety:
    def test_save_publishes_checksum_sidecar(self, cache_dir):
        from log_parser_tpu.patterns.bank import PatternBank

        PatternBank(_sets())
        path = _snapshot(cache_dir)
        sidecar = path.with_name(path.name + ".sum")
        assert sidecar.exists()
        digest, size = sidecar.read_text().split()
        blob = path.read_bytes()
        assert digest == hashlib.sha256(blob).hexdigest()
        assert int(size) == len(blob)

    def test_flipped_byte_quarantined_and_rebuilt(self, cache_dir):
        """A single flipped byte mid-file — the torn-write/bit-rot case a
        bare ``pickle.load`` may well decode into silent garbage — is
        caught by the checksum, quarantined, and rebuilt cold."""
        from log_parser_tpu.patterns.bank import PatternBank

        PatternBank(_sets())
        path = _snapshot(cache_dir)
        blob = bytearray(path.read_bytes())
        blob[len(blob) // 2] ^= 0xFF
        path.write_bytes(bytes(blob))

        bank = PatternBank(_sets())  # must not raise
        assert bank.n_patterns == 2
        corrupt = list((cache_dir / "bank").glob("*.pkl.corrupt"))
        assert len(corrupt) == 1
        # the rebuild republished a healthy snapshot + fresh sidecar
        path = _snapshot(cache_dir)
        assert (
            path.with_name(path.name + ".sum").read_text().split()[0]
            == hashlib.sha256(path.read_bytes()).hexdigest()
        )

    def test_truncated_entry_quarantined_and_rebuilt(self, cache_dir):
        from log_parser_tpu.patterns.bank import PatternBank

        PatternBank(_sets())
        path = _snapshot(cache_dir)
        blob = path.read_bytes()
        path.write_bytes(blob[: len(blob) // 2])

        bank = PatternBank(_sets())
        assert bank.n_patterns == 2
        assert list((cache_dir / "bank").glob("*.pkl.corrupt"))

    def test_sidecarless_legacy_entry_still_loads(self, cache_dir):
        from log_parser_tpu.patterns import libcache
        from log_parser_tpu.patterns.bank import PatternBank

        PatternBank(_sets())
        path = _snapshot(cache_dir)
        path.with_name(path.name + ".sum").unlink()
        key = path.stem
        assert libcache.load(key) is not None  # trusted, like before

    def test_corrupt_rebuild_scores_match_cold_build(self, cache_dir):
        """Acceptance: startup over a corrupted entry succeeds AND the
        rebuilt bank scores identically to a cold build."""
        from log_parser_tpu.config import ScoringConfig
        from log_parser_tpu.models.pod import PodFailureData
        from log_parser_tpu.runtime import AnalysisEngine

        logs = "ok\njava.lang.OutOfMemoryError: heap\na timeout b"
        data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=logs)
        r_cold = AnalysisEngine(_sets(), ScoringConfig()).analyze(data)

        path = _snapshot(cache_dir)
        blob = bytearray(path.read_bytes())
        blob[10] ^= 0x55
        path.write_bytes(bytes(blob))

        r_rebuilt = AnalysisEngine(_sets(), ScoringConfig()).analyze(data)
        assert [(e.matched_pattern.id, e.line_number, e.score)
                for e in r_rebuilt.events] == [
            (e.matched_pattern.id, e.line_number, e.score)
            for e in r_cold.events
        ]
        assert len(r_cold.events) == 2

    def test_injected_cache_fault_is_a_miss_not_a_quarantine(self, cache_dir):
        from log_parser_tpu.patterns import libcache
        from log_parser_tpu.patterns.bank import PatternBank

        PatternBank(_sets())
        path = _snapshot(cache_dir)
        key = path.stem

        faults.install(FaultRegistry.parse("cache_raise@times=1"))
        assert libcache.load(key) is None  # injected read failure: a miss
        assert path.exists()  # the healthy entry was NOT quarantined
        assert not list((cache_dir / "bank").glob("*.pkl.corrupt"))
        assert libcache.load(key) is not None  # budget spent: loads again


# ----------------------------------------------------------- xlacache


class TestXlaCacheIntegrity:
    def _entry(self, d, name, content):
        path = os.path.join(d, name)
        with open(path, "wb") as f:
            f.write(content)
        return path

    def test_sweep_records_then_detects_corruption(self, tmp_path):
        from log_parser_tpu.utils.xlacache import verify_cache_integrity

        d = str(tmp_path)
        self._entry(d, "exec-a", b"compiled-bytes-a" * 100)
        self._entry(d, "exec-b", b"compiled-bytes-b" * 100)

        first = verify_cache_integrity(d)
        assert first == {"checked": 2, "recorded": 2, "quarantined": 0}
        assert sorted(os.listdir(os.path.join(d, ".integrity"))) == [
            "exec-a.sum", "exec-b.sum",
        ]

        # truncate one entry the way a crashed writer would
        with open(os.path.join(d, "exec-a"), "wb") as f:
            f.write(b"compiled")
        second = verify_cache_integrity(d)
        assert second["quarantined"] == 1
        assert not os.path.exists(os.path.join(d, "exec-a"))
        assert os.path.exists(os.path.join(d, "exec-a.corrupt"))
        assert os.path.exists(os.path.join(d, "exec-b"))

        # the quarantined name is now a plain miss: sweeps stay stable
        third = verify_cache_integrity(d)
        assert third == {"checked": 1, "recorded": 0, "quarantined": 0}

    def test_unmodified_entries_pass_repeated_sweeps(self, tmp_path):
        from log_parser_tpu.utils.xlacache import verify_cache_integrity

        d = str(tmp_path)
        self._entry(d, "exec-a", b"stable" * 1000)
        verify_cache_integrity(d)
        for _ in range(3):
            counts = verify_cache_integrity(d)
            assert counts == {"checked": 1, "recorded": 0, "quarantined": 0}

    def test_mutable_atime_markers_are_never_checksummed(self, tmp_path):
        from log_parser_tpu.utils.xlacache import verify_cache_integrity

        d = str(tmp_path)
        self._entry(d, "jit_f-abc123-cache", b"payload" * 100)
        self._entry(d, "jit_f-abc123-atime", b"\x00" * 8)

        first = verify_cache_integrity(d)
        assert first == {"checked": 1, "recorded": 1, "quarantined": 0}

        # JAX rewrites the atime marker on every cache hit; the sweep
        # must not mistake that for corruption
        self._entry(d, "jit_f-abc123-atime", b"\x01" * 8)
        second = verify_cache_integrity(d)
        assert second == {"checked": 1, "recorded": 0, "quarantined": 0}
        assert os.path.exists(os.path.join(d, "jit_f-abc123-atime"))

    def test_orphan_sidecars_are_dropped(self, tmp_path):
        from log_parser_tpu.utils.xlacache import verify_cache_integrity

        d = str(tmp_path)
        path = self._entry(d, "exec-a", b"bytes")
        verify_cache_integrity(d)
        os.unlink(path)  # operator cleanup (find -atime +30 -delete)
        verify_cache_integrity(d)
        assert os.listdir(os.path.join(d, ".integrity")) == []

    def test_missing_directory_is_a_noop(self, tmp_path):
        from log_parser_tpu.utils.xlacache import verify_cache_integrity

        counts = verify_cache_integrity(str(tmp_path / "never-created"))
        assert counts == {"checked": 0, "recorded": 0, "quarantined": 0}

    def test_injected_cache_fault_aborts_sweep_quietly(self, tmp_path):
        from log_parser_tpu.utils.xlacache import verify_cache_integrity

        d = str(tmp_path)
        self._entry(d, "exec-a", b"bytes")
        faults.install(FaultRegistry.parse("cache_raise@times=1"))
        counts = verify_cache_integrity(d)  # must not raise into boot
        assert counts == {"checked": 0, "recorded": 0, "quarantined": 0}
        assert os.path.exists(os.path.join(d, "exec-a"))


class TestXlaCachePlacement:
    """Where the persistent compile cache lives: JAX's own env var when
    set (and then the program sets no directory), else a fixed path in
    the checkout; ``LOG_PARSER_TPU_XLA_CACHE=0`` turns it off."""

    @pytest.fixture
    def enable(self, monkeypatch, tmp_path):
        import jax

        from log_parser_tpu.utils import xlacache

        updates: dict = {}
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: updates.__setitem__(k, v)
        )
        monkeypatch.setattr(xlacache, "_configured", False)
        monkeypatch.setattr(xlacache, "_cache_dir", None)
        monkeypatch.setattr(xlacache, "_listener_registered", True)
        monkeypatch.setattr(xlacache, "DEFAULT_DIR", str(tmp_path / "default"))
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.delenv("LOG_PARSER_TPU_XLA_CACHE", raising=False)

        def run() -> dict:
            xlacache.enable_persistent_cache()
            return updates

        return run

    def test_default_is_a_fixed_path_in_the_checkout(self, enable, tmp_path):
        from log_parser_tpu.utils import xlacache

        updates = enable()
        assert updates["jax_compilation_cache_dir"] == str(tmp_path / "default")
        assert updates["jax_persistent_cache_min_entry_size_bytes"] == 0
        assert xlacache.stats()["dir"] == str(tmp_path / "default")
        assert os.path.isdir(tmp_path / "default")

    def test_default_dir_constant(self):
        from log_parser_tpu.utils import xlacache

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert xlacache.DEFAULT_DIR == os.path.join(repo, ".cache", "xla")

    def test_jax_env_var_wins_and_no_dir_is_set(
        self, enable, monkeypatch, tmp_path
    ):
        from log_parser_tpu.utils import xlacache

        target = tmp_path / "from-env"
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(target))
        updates = enable()
        assert "jax_compilation_cache_dir" not in updates
        # thresholds and the integrity sweep still apply
        assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
        assert os.path.isdir(target / ".integrity")
        assert xlacache.stats() == {
            "dir": str(target), "enabled": True,
            "compileHits": xlacache._hits,
            "compileMisses": max(0, xlacache._requests - xlacache._hits),
        }

    @pytest.mark.parametrize("value", ["0", " 0 "])
    def test_zero_disables(self, enable, monkeypatch, value):
        from log_parser_tpu.utils import xlacache

        monkeypatch.setenv("LOG_PARSER_TPU_XLA_CACHE", value)
        assert enable() == {}
        assert xlacache.stats()["enabled"] is False

    def test_directory_value_is_no_longer_an_override(
        self, enable, monkeypatch, tmp_path
    ):
        monkeypatch.setenv("LOG_PARSER_TPU_XLA_CACHE", str(tmp_path / "old"))
        updates = enable()
        assert updates["jax_compilation_cache_dir"] == str(tmp_path / "default")
        assert not os.path.exists(tmp_path / "old")
