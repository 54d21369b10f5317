"""Bench harness regression tests (bench_common.py).

The contract under test: a bench measures the chip or nothing. With no
TPU the in-process device check emits a ``{"value": null}`` line and
exits 3 — there is no CPU floor — and wedged phases stay bounded.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

import pytest

import bench_common


def test_run_campaign_measures_levels():
    curve, err = bench_common.run_campaign(
        lambda: time.sleep(0.001), n_lines=100, campaign_s=0.2, levels=(2, 1)
    )
    assert err is None
    assert [p["concurrency"] for p in curve] == [1, 2]  # sorted output
    assert all(p["requests"] > 0 and p["lines_per_sec"] > 0 for p in curve)


def test_run_campaign_degrades_on_error():
    """A failing level is recorded and ends the campaign instead of
    destroying it (the pre-round-4 behavior was raise-on-first-error)."""

    def analyze():
        raise ValueError("backend died")

    curve, err = bench_common.run_campaign(analyze, 100, campaign_s=0.2, levels=(2, 1))
    assert err is not None and err.startswith("concurrency 2:")
    assert "backend died" in err
    assert [p["concurrency"] for p in curve] == [2]
    assert "backend died" in curve[0]["error"]
    assert len(curve[0]["error"]) <= 300


def test_run_campaign_detects_wedged_level(monkeypatch):
    """Requests that never return must trip the bounded drain and degrade
    the level, not hang the bench forever."""
    monkeypatch.setattr(bench_common, "DRAIN_FLOOR_S", 0.3)
    release = threading.Event()
    try:
        curve, err = bench_common.run_campaign(
            release.wait, 100, campaign_s=0.1, levels=(1, 2)
        )
        assert err is not None and "wedged" in err
        assert curve[0]["concurrency"] == 1 and "wedged" in curve[0]["error"]
        assert len(curve) == 1  # nothing after the wedged level ran
    finally:
        release.set()  # let the leaked daemon client threads exit


def test_run_bounded_returns_results_in_order():
    out = bench_common.run_bounded(
        [lambda: 1, lambda: 2, lambda: 3], 10.0, "m", "u", "p", "phase"
    )
    assert out == [1, 2, 3]


def test_run_bounded_reraises_worker_error():
    def boom():
        raise ValueError("backend died")

    with pytest.raises(ValueError, match="backend died"):
        bench_common.run_bounded([boom], 10.0, "m", "u", "p", "phase")


def test_run_bounded_wedge_exits_with_null_artifact(capsys):
    """A worker that never returns must produce the exit-3 diagnostics
    line, never an unbounded hang — the harness contract every bench
    (latency, mesh) now rides on."""
    release = threading.Event()
    try:
        with pytest.raises(SystemExit) as exc_info:
            bench_common.run_bounded([release.wait], 0.2, "m", "u", "p", "phase")
        assert exc_info.value.code == 3
        out = capsys.readouterr().out
        assert '"value": null' in out
        assert "wedged" in out
    finally:
        release.set()


def test_bench_mesh_smoke():
    """bench_mesh end-to-end at tiny shapes on a 2-device virtual mesh.
    The suite env carries an 8-device XLA_FLAGS count from conftest, so
    this also exercises the stale-flag replacement (--devices must win).
    """
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo  # hermetic: drops any device plugin
    # a stall anywhere must surface as the bench's own diagnostics exit,
    # not a bare TimeoutExpired; the healthy path finishes in ~30s
    r = subprocess.run(
        [sys.executable, "bench_mesh.py", "--devices", "2", "--lines", "200"],
        capture_output=True,
        text=True,
        timeout=1100,
        cwd=repo,
        env=env,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    import json

    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["platform"] == "cpu-virtual-mesh2"
    assert doc["n_devices"] == 2 and doc["value"] > 0 and doc["n_events"] > 0
    # OBSERVED device count, not an echo of --devices: proves the
    # stale 8-device flag from conftest was actually replaced
    assert doc["visible_devices"] == 2


def test_emit_stamps_host_load(monkeypatch, capsys):
    import json

    monkeypatch.setattr(bench_common, "last_device", None)
    bench_common.emit("m", 1.0, "u", None, "tpu")
    doc = json.loads(capsys.readouterr().out)
    # bench honesty: every artifact records what else the box was doing
    load = doc["host_load"]
    assert len(load["loadavg"]) == 3
    assert all(x >= 0 for x in load["loadavg"])
    assert load["cpus"] == os.cpu_count()
    assert "device" not in doc


def test_require_tpu_refuses_cpu(monkeypatch, capsys):
    """The suite runs on the CPU backend: the device check must exit 3
    with the null line naming what JAX found — never a CPU floor."""
    import json

    monkeypatch.setattr(bench_common, "last_device", None)
    with pytest.raises(SystemExit) as exc_info:
        bench_common.require_tpu("m", "u")
    assert exc_info.value.code == 3
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["value"] is None and doc["platform"] == "cpu"
    assert "no TPU" in doc["error"]
    assert doc["device"]["platform"] == "cpu"


def test_require_tpu_accepts_tpu_and_emit_stamps_device(monkeypatch, capsys):
    import json

    import jax

    class _Tpu:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(bench_common, "last_device", None)
    monkeypatch.setattr(jax, "devices", lambda *a: [_Tpu()])
    assert bench_common.require_tpu("m", "u") == "tpu"
    bench_common.emit("m", 1.0, "u", None, "tpu")
    doc = json.loads(capsys.readouterr().out)
    assert doc["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1}


@pytest.mark.parametrize(
    "argv",
    [
        ["bench.py", "--lines", "64"],
        ["bench_bank.py", "--patterns", "8", "--lines", "64"],
        ["bench_latency.py", "--requests", "2"],
    ],
    ids=["bench", "bench_bank", "bench_latency"],
)
def test_bench_refuses_without_chip(argv):
    """Each bench, run as a user would on a host with no TPU, exits 3
    with a null line and never prints a number."""
    import json

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    r = subprocess.run(
        [sys.executable, *argv], capture_output=True, text=True,
        timeout=300, cwd=repo, env=env,
    )
    assert r.returncode == 3, r.stderr[-2000:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["value"] is None and doc["platform"] == "cpu"


def test_bench_diff_marks_unequal_load_advisory(tmp_path):
    import json

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        import bench_diff
    finally:
        sys.path.pop(0)
    busy = {"metric": "lines_per_sec", "value": 100.0,
            "host_load": {"loadavg": [12.0, 10.0, 8.0], "cpus": 8}}
    quiet = {"metric": "lines_per_sec", "value": 50.0,
             "host_load": {"loadavg": [0.2, 0.2, 0.2], "cpus": 8}}
    adv = bench_diff.load_advisory(busy, quiet)
    assert adv is not None and adv["ratio"] > 2.0
    # comparable load (or a pre-stamp artifact) stays trustworthy
    assert bench_diff.load_advisory(quiet, dict(quiet)) is None
    assert bench_diff.load_advisory({}, quiet) is None
    # end-to-end: --strict must NOT fail a 2x "regression" measured on
    # a loaded box, and the JSON summary carries the advisory
    a, b = tmp_path / "old.json", tmp_path / "new.json"
    a.write_text(json.dumps(busy))
    b.write_text(json.dumps(quiet))
    assert bench_diff.main([str(a), str(b), "--strict"]) == 0
