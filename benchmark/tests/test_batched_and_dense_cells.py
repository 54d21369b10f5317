"""The micro-batched cell and the hit-dense cell rehearsed traced on the
CPU at a tiny size (``tiny.shrink``): each is correct and reports every
per-layer metric listed for it but the device's idle share, which needs
a chip's trace."""

from __future__ import annotations

import pytest

from benchmark.cell import load_benchmark
from benchmark.tests.test_rehearsal import DEVICE_METRICS, run_cell

TAIL = "builtin83_batched.tail_flush"
DENSE = "builtin83.hit_dense"


def _listed(cell: str) -> set[str]:
    return {m["name"] for m in load_benchmark()["per_layer"]
            if cell in m.get("workloads", ())}


@pytest.mark.parametrize("cell", [TAIL, DENSE])
def test_traced_cell_reports_its_per_layer_metrics(monkeypatch, tmp_path,
                                                   capsys, cell):
    result, out, err = run_cell(monkeypatch, tmp_path, capsys, cell, trace=1)
    assert result["correct"] is True, err[-2000:]
    assert result["failed"] == 0
    assert "compiles_in_window=0 " in out
    assert set(result["metrics"]) == _listed(cell) - DEVICE_METRICS
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    if cell == TAIL:
        assert metrics["requests_per_flush.tail"] >= 1
        assert 0 < metrics["residual_fill_share.tail"] <= 1
        assert metrics["flush_ms.tail"] > 0
        assert metrics["batch_wait_ms.tail"] >= 0
    else:
        # every line unique, so the cache only misses
        assert metrics["cache_hit_share.bulk"] == 0.0
