"""Test harness configuration.

Forces JAX onto a virtual 8-device CPU backend *before* jax is imported
anywhere, so `shard_map`/mesh tests exercise real multi-device sharding
without TPU hardware (the standard JAX fake-backend idiom — SURVEY.md §4).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# device bugs must never hide behind the golden-host insurance path; the
# fallback itself is tested explicitly with it re-enabled (test_fallback.py)
os.environ["LOG_PARSER_TPU_NO_FALLBACK"] = "1"

# keep the suite hermetic: never read or write the user-level persistent
# XLA executable cache (entries written under different XLA_FLAGS emit
# machine-feature mismatch warnings on load)
os.environ["LOG_PARSER_TPU_XLA_CACHE"] = "0"

# ... and never the user-level DFA/bank/AC caches either: a warm bank
# snapshot would silently bypass the bank-construction code a test run is
# meant to exercise. One shared per-run directory keeps repeat builds
# within the run fast (tests that need cold/warm control, like
# test_libcache.py, monkeypatch LOG_PARSER_TPU_CACHE themselves).
import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

_cache_root = tempfile.mkdtemp(prefix="lpt-test-cache-")
os.environ["LOG_PARSER_TPU_CACHE"] = _cache_root
atexit.register(shutil.rmtree, _cache_root, ignore_errors=True)

import pytest  # noqa: E402

from log_parser_tpu.config import ScoringConfig  # noqa: E402


@pytest.fixture
def default_config() -> ScoringConfig:
    return ScoringConfig()


class FakeClock:
    """Deterministic, manually-advanced clock for frequency-window tests."""

    def __init__(self, start: float = 0.0):
        self.now = start

    def __call__(self) -> float:
        return self.now

    def advance(self, seconds: float) -> None:
        self.now += seconds


@pytest.fixture
def fake_clock() -> FakeClock:
    return FakeClock()
