"""Every cell rehearsed end to end on the CPU for about two seconds at a
tiny size (``tiny.shrink``), with the harness's platform check steered
here and not through an option of the harness. Unsteered, the harness
refuses the CPU and prints no result."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchmark import run
from benchmark.cell import ROOT, load_benchmark
from benchmark.tests import tiny

CELLS = [w["name"] for w in load_benchmark()["workloads"]]
DEVICE_METRICS = {m["name"] for m in load_benchmark()["per_layer"]
                  if m["source"] == "device_trace"}


def run_cell(monkeypatch, tmp_path, capsys, name, trace=0, transform=tiny.shrink,
             seed=4000000007):
    tiny.steer(monkeypatch, tmp_path, run, transform)
    assert run.main(["--workload", name, "--seed", str(seed), "--seconds", "2",
                     "--trace", str(trace)]) == 0
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), out, err


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(monkeypatch, tmp_path, capsys, name):
    result, out, err = run_cell(monkeypatch, tmp_path, capsys, name)
    assert result["correct"] is True, err[-2000:]
    assert result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert "setup_s" in result["metrics"]
    assert list(result)[-1] == "checks"
    assert "compiles_in_window=0 " in out
    # the numbers compared are the last lines of standard error
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert [line.split(":")[0] for line in tail] == [
        f"check {k}" for k in result["checks"]
    ]


def test_traced_run_reports_no_device_number_from_the_cpu(monkeypatch, tmp_path,
                                                          capsys):
    result, out, _ = run_cell(monkeypatch, tmp_path, capsys,
                              "builtin83.triage_open", trace=1)
    assert result["correct"] is True
    assert "engine_ms.triage" in result["metrics"]
    assert not DEVICE_METRICS & set(result["metrics"])
    assert "busy_s" not in result["device"]
    assert "trace: no device plane" in out


def test_refuses_the_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env,
    )
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "needs 1 'tpu'" in r.stderr


def test_unknown_cell_is_refused(capsys):
    assert run.main(["--workload", "nope.nothing", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert '"correct"' not in capsys.readouterr().out
