"""The four-chip cell rehearsed traced on four virtual CPU devices
(``benchmark/conftest.py``): every per-layer metric listed for it is in
its line but the device's idle share, which needs a chip's trace."""

from __future__ import annotations

from benchmark.cell import load_benchmark
from benchmark.tests.test_rehearsal import DEVICE_METRICS, run_cell

CELL = "builtin83.mesh4_bulk"


def test_traced_mesh_cell_reports_its_per_layer_metrics(monkeypatch, tmp_path,
                                                        capsys):
    listed = {m["name"] for m in load_benchmark()["per_layer"]
              if CELL in m.get("workloads", ())}
    result, out, _ = run_cell(monkeypatch, tmp_path, capsys, CELL, trace=1)
    assert result["correct"] is True
    assert result["device"]["count"] == 4
    assert set(result["metrics"]) == listed - DEVICE_METRICS
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["relaunches_per_request.mesh"] == 0.0
    assert metrics["exchange_bytes_per_line.mesh"] > 0
    assert 0 < metrics["record_fill_share.mesh"] <= 1
    assert "trace: no device plane" in out
