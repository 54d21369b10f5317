"""The line cache's per-layer time (``cache_s_per_mline.bulk``,
``cache_ms.triage``): the ``cache`` phase plus the ``cache.populate``
stage, read in a traced rehearsal on the CPU, and left out, not raised,
where the program records no ``cache.populate`` stage."""

from __future__ import annotations

import json
import math

import pytest

from benchmark import run
from benchmark.cell import load_benchmark, metric_reader
from benchmark.measure import Run
from benchmark.tests import tiny

CACHE_METRICS = {
    m["name"]: m["workloads"] for m in load_benchmark()["per_layer"]
    if m["name"] in {"cache_s_per_mline.bulk", "cache_ms.triage"}
}
LABELS = (("route", "device"), ("tenant", "default"))


def _run(after_stage: float | None) -> Run:
    window = [{"status": 200, "lines": 250_000, "sent": 0.0, "done": 0.5,
               "due": 0.0, "warmup": False}] * 2
    phase = ("logparser_phase_seconds_sum", (("phase", "cache"),) + LABELS)
    before = {phase: 1.0}
    after = {phase: 2.0}
    if after_stage is not None:
        stage = (("stage", "cache.populate"), ("tenant", "default"))
        before[("logparser_stage_seconds_sum", stage)] = 0.25
        before[("logparser_stage_seconds_count", stage)] = 1.0
        after[("logparser_stage_seconds_sum", stage)] = after_stage
        after[("logparser_stage_seconds_count", stage)] = 3.0
    return Run(1.0, 1.0, window, before, after)


def test_both_are_declared_for_the_cells_with_a_line_cache():
    assert CACHE_METRICS == {
        "cache_s_per_mline.bulk": ["builtin83.bulk_unique",
                                   "synth10k.bulk_unique",
                                   "builtin83.bulk_rr90"],
        "cache_ms.triage": ["builtin83.triage_open"],
    }


@pytest.mark.parametrize("name", sorted(CACHE_METRICS))
def test_a_program_without_the_populate_stage_reads_nothing(name):
    assert metric_reader(name)(_run(None)) is None


def test_the_sum_of_phase_and_stage():
    # 1 s of cache phase + 0.5 s of populate over 0.5 Mline in 2 requests
    r = _run(0.75)
    assert metric_reader("cache_s_per_mline.bulk")(r) == pytest.approx(3.0)
    assert metric_reader("cache_ms.triage")(r) == pytest.approx(750.0)


@pytest.mark.parametrize("cell", ["builtin83.bulk_unique", "builtin83.triage_open"])
def test_traced_rehearsal_reports_the_cache_metric(monkeypatch, tmp_path, capsys,
                                                   cell):
    tiny.steer(monkeypatch, tmp_path, run)
    assert run.main(["--workload", cell, "--seed", "3000000023",
                     "--seconds", "2", "--trace", "1"]) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True
    (name,) = [n for n, cells in CACHE_METRICS.items() if cell in cells]
    value = result["metrics"][name]["value"]
    assert math.isfinite(value) and value > 0
