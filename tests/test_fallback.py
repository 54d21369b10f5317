"""Failure containment (SURVEY.md §5.3): a dead device batch falls back to
the golden host path — same result, same frequency-state evolution. Only
device/XLA-layer errors may degrade; logic bugs propagate."""

from __future__ import annotations

import time

import jax.errors
import pytest

from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.golden import GoldenAnalyzer
from log_parser_tpu.models import PodFailureData
from log_parser_tpu.runtime import AnalysisEngine
from log_parser_tpu.runtime.engine import is_device_error

from conftest import FakeClock
from helpers import make_pattern, make_pattern_set
from test_engine_parity import assert_results_match

LOGS = "ok\nERROR boom\nok\nERROR again"


def _sets():
    return [make_pattern_set([make_pattern("e", regex="ERROR", confidence=0.7)])]


def test_device_failure_served_by_golden(monkeypatch):
    engine = AnalysisEngine(_sets(), ScoringConfig(), clock=FakeClock())
    engine.fallback_to_golden = True

    def boom(*a, **k):
        raise jax.errors.JaxRuntimeError("injected device loss")

    monkeypatch.setattr(engine, "_run_device", boom)
    golden = GoldenAnalyzer(_sets(), ScoringConfig(), clock=FakeClock())
    data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=LOGS)
    assert_results_match(engine.analyze(data), golden.analyze(data))
    # the fallback recorded into the SAME tracker the device path uses
    assert engine.frequency.get_frequency_statistics() == {"e": 2}
    assert engine.fallback_count == 1


def test_late_failure_rolls_back_frequency_state(monkeypatch):
    """A device request that dies AFTER recording its matches must not
    leave the tracker double-counted when golden re-serves it."""
    import log_parser_tpu.runtime.engine as engine_mod

    engine = AnalysisEngine(_sets(), ScoringConfig(), clock=FakeClock())
    engine.fallback_to_golden = True

    def boom(events):
        # device errors can surface this late: transfers are async, so a
        # dead chip is often first observed at np.asarray() time downstream
        raise jax.errors.JaxRuntimeError("injected post-record failure")

    monkeypatch.setattr(engine_mod, "build_summary", boom)
    golden = GoldenAnalyzer(_sets(), ScoringConfig(), clock=FakeClock())
    data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=LOGS)
    r1, r2 = engine.analyze(data), golden.analyze(data)
    assert [e.score for e in r1.events] == [e.score for e in r2.events]
    # exactly one batch recorded — not the device batch plus the golden one
    assert engine.frequency.get_frequency_statistics() == {"e": 2}
    assert engine.last_trace is None and engine.last_finalized is None


def test_fallback_disabled_raises(monkeypatch):
    engine = AnalysisEngine(_sets(), ScoringConfig())
    engine.fallback_to_golden = False
    monkeypatch.setattr(
        engine,
        "_run_device",
        lambda *a, **k: (_ for _ in ()).throw(jax.errors.JaxRuntimeError("x")),
    )
    data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=LOGS)
    with pytest.raises(RuntimeError):
        engine.analyze(data)


def test_logic_bug_propagates_despite_fallback(monkeypatch):
    """A non-device bug must NOT be masked by the golden fallback — round-1
    regression: a masked failure re-served a 200k-line bench from pure
    Python and turned a fast failure into a timeout (VERDICT.md weak #1)."""
    engine = AnalysisEngine(_sets(), ScoringConfig(), clock=FakeClock())
    engine.fallback_to_golden = True

    monkeypatch.setattr(
        engine,
        "_run_device",
        lambda *a, **k: (_ for _ in ()).throw(TypeError("assembly bug")),
    )
    data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=LOGS)
    with pytest.raises(TypeError):
        engine.analyze(data)
    assert engine.fallback_count == 0


def _raised_from(module_name: str, msg: str) -> RuntimeError:
    """Raise-and-catch a RuntimeError from a frame whose module is
    ``module_name`` (simulates an error originating inside jax/jaxlib)."""
    g = {"__name__": module_name, "__builtins__": __builtins__}
    exec("def r(msg):\n    raise RuntimeError(msg)", g)
    try:
        g["r"](msg)
    except RuntimeError as exc:
        return exc
    raise AssertionError("unreachable")


def test_is_device_error_classification():
    assert is_device_error(jax.errors.JaxRuntimeError("boom"))
    # device-layer marker AND raised from a jax frame → device error
    assert is_device_error(
        _raised_from("jax._src.xla_bridge", "Unable to initialize backend 'tpu'")
    )
    assert is_device_error(_raised_from("jaxlib.xla_client", "DEADLINE_EXCEEDED: poll"))
    # marker text quoted by NON-jax code must propagate (ADVICE.md r2): a
    # log line or downstream response embedding "UNAVAILABLE" is not a
    # device failure
    assert not is_device_error(
        RuntimeError("downstream said: UNAVAILABLE, Unable to initialize backend")
    )
    assert not is_device_error(
        _raised_from("log_parser_tpu.runtime.engine", "quoting UNAVAILABLE text")
    )
    # jax frame but no marker → still not classified as a device error
    assert not is_device_error(_raised_from("jax._src.core", "some tracing bug"))
    assert not is_device_error(RuntimeError("some unrelated runtime issue"))
    assert not is_device_error(TypeError("bug"))
    assert not is_device_error(ValueError("bad value"))


def test_frequency_snapshot_roundtrip():
    clock = FakeClock()
    engine = AnalysisEngine(_sets(), ScoringConfig(), clock=clock)
    data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=LOGS)
    engine.analyze(data)
    engine.analyze(data)
    snap = engine.frequency.snapshot()
    assert snap == {"e": [0.0, 0.0, 0.0, 0.0]}

    # a fresh process (same clock model) restores to identical state
    clock2 = FakeClock()
    engine2 = AnalysisEngine(_sets(), ScoringConfig(), clock=clock2)
    engine2.frequency.restore(snap)
    assert engine2.frequency.get_frequency_statistics() == {"e": 4}
    # scores after restore match continuing with the original engine
    r1 = engine.analyze(data)
    r2 = engine2.analyze(data)
    assert [e.score for e in r1.events] == [e.score for e in r2.events]


def test_logic_bug_rolls_back_frequency_state(monkeypatch):
    """Even a propagating (non-device) failure must not leak its partial
    match counts into the tracker — a client retry would double-count."""
    import log_parser_tpu.runtime.engine as engine_mod

    engine = AnalysisEngine(_sets(), ScoringConfig(), clock=FakeClock())
    engine.fallback_to_golden = True

    monkeypatch.setattr(
        engine_mod,
        "build_summary",
        lambda events: (_ for _ in ()).throw(TypeError("assembly bug")),
    )
    data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=LOGS)
    with pytest.raises(TypeError):
        engine.analyze(data)  # matches were recorded before the failure
    # rolled back to the pre-request (empty) tracker state
    assert engine.frequency.get_frequency_statistics() == {}
    assert not engine.frequency.has_entry("e")


def test_no_fallback_late_failure_still_rolls_back(monkeypatch):
    """The rollback invariant holds on the fallback-DISABLED path too
    (LOG_PARSER_TPU_NO_FALLBACK=1 servers return a 500; the retry must not
    double-count)."""
    import log_parser_tpu.runtime.engine as engine_mod

    engine = AnalysisEngine(_sets(), ScoringConfig(), clock=FakeClock())
    engine.fallback_to_golden = False

    monkeypatch.setattr(
        engine_mod,
        "build_summary",
        lambda events: (_ for _ in ()).throw(TypeError("assembly bug")),
    )
    data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=LOGS)
    with pytest.raises(TypeError):
        engine.analyze(data)
    assert engine.frequency.get_frequency_statistics() == {}


def test_restore_replaces_all_state():
    """restore() rebuilds from the snapshot — ids absent from the payload
    are cleared, not merged (round-1 advisor finding)."""
    clock = FakeClock()
    engine = AnalysisEngine(_sets(), ScoringConfig(), clock=clock)
    engine.analyze(PodFailureData(pod={"metadata": {"name": "p"}}, logs=LOGS))
    assert engine.frequency.get_frequency_statistics() == {"e": 2}

    engine.frequency.restore({"other": [1.0, 2.0]})
    assert engine.frequency.get_frequency_statistics() == {"other": 2}
    assert not engine.frequency.has_entry("e")


def test_restore_rejects_negative_ages():
    """Negative ages are future timestamps that never prune; the whole
    payload is rejected before any state is touched (all-or-nothing)."""
    clock = FakeClock()
    engine = AnalysisEngine(_sets(), ScoringConfig(), clock=clock)
    engine.analyze(PodFailureData(pod={"metadata": {"name": "p"}}, logs=LOGS))
    with pytest.raises(ValueError):
        engine.frequency.restore({"e": [1.0], "x": [-0.5]})
    # prior state untouched
    assert engine.frequency.get_frequency_statistics() == {"e": 2}


def test_is_device_error_walks_cause_chain():
    """jax's traceback filtering strips jax frames from the primary
    traceback and re-parents the unfiltered exception via __cause__ —
    classification must follow the chain."""
    inner = _raised_from("jax._src.xla_bridge", "Unable to initialize backend 'tpu'")
    try:
        raise RuntimeError("Unable to initialize backend 'tpu'") from inner
    except RuntimeError as outer:
        assert is_device_error(outer)
    # implicit chaining (__context__) counts too
    try:
        try:
            raise _raised_from("jaxlib.xla_client", "UNAVAILABLE: socket closed")
        except RuntimeError:
            raise RuntimeError("UNAVAILABLE: socket closed")
    except RuntimeError as outer:
        assert is_device_error(outer)


def test_watchdog_hang_trips_circuit_and_recovers(monkeypatch):
    """A wedged device step (never returns) times out, serves from
    golden, opens the circuit (immediate fallback, no thread stacking),
    and the circuit closes when the hung worker finally responds."""
    import threading

    from log_parser_tpu.runtime.engine import DeviceWatchdog

    engine = AnalysisEngine(_sets(), ScoringConfig(), clock=FakeClock())
    engine.fallback_to_golden = True
    engine.watchdog = DeviceWatchdog(timeout_s=0.2)
    release = threading.Event()
    real_run = engine._run_device
    hang = {"on": True}
    started = []

    def wedged(*a, **k):
        if hang["on"]:
            started.append(1)
            release.wait(10)
        return real_run(*a, **k)

    monkeypatch.setattr(engine, "_run_device", wedged)
    golden = GoldenAnalyzer(_sets(), ScoringConfig(), clock=FakeClock())
    data = PodFailureData(pod={"metadata": {"name": "p"}}, logs=LOGS)

    # 1) hang -> timeout -> golden serves; circuit opens
    assert_results_match(engine.analyze(data), golden.analyze(data))
    assert engine.fallback_count == 1 and engine.watchdog.circuit_open

    # 2) circuit open: immediate fallback, the wedged fn is NOT re-entered
    assert_results_match(engine.analyze(data), golden.analyze(data))
    assert engine.fallback_count == 2 and len(started) == 1

    # 3) backend recovers: hung worker completes, circuit closes,
    #    the next request runs on the device again
    hang["on"] = False
    release.set()
    deadline = time.time() + 5
    while engine.watchdog.circuit_open and time.time() < deadline:
        time.sleep(0.01)
    assert not engine.watchdog.circuit_open
    assert_results_match(engine.analyze(data), golden.analyze(data))
    assert engine.fallback_count == 2  # served by the device this time


def test_watchdog_disabled_runs_inline():
    from log_parser_tpu.runtime.engine import DeviceWatchdog

    wd = DeviceWatchdog(timeout_s=0)
    calls = []
    assert wd.run(lambda: calls.append(1) or 42) == 42
    assert calls == [1] and not wd.circuit_open


def test_watchdog_propagates_worker_errors():
    """Errors from the device step pass through the watchdog unchanged
    (device errors keep their class for is_device_error)."""
    from log_parser_tpu.runtime.engine import DeviceWatchdog

    wd = DeviceWatchdog(timeout_s=5.0)

    def boom():
        raise jax.errors.JaxRuntimeError("injected")

    with pytest.raises(jax.errors.JaxRuntimeError):
        wd.run(boom)
    assert not wd.circuit_open
