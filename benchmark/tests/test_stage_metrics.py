"""The per-layer metrics read from the program's stage spans and CPU
counters (``benchmark/stages.py``): present and finite in a traced
rehearsal of one bulk cell and the triage cell on the CPU, with no
device-trace metric, and left out, not raised, where the program does
not record them."""

from __future__ import annotations

import json
import math

import pytest

from benchmark import run
from benchmark.cell import load_benchmark, metric_reader
from benchmark.measure import Run
from benchmark.tests import tiny

PER_LAYER = load_benchmark()["per_layer"]
STAGE_METRICS = {
    m["name"]: m["workloads"] for m in PER_LAYER
    if m["name"] in {
        "transport_in_s_per_mline.bulk", "transport_out_s_per_mline.bulk",
        "device_copy_s_per_mline.bulk", "device_wait_s_per_mline.bulk",
        "finish_cpu_share.bulk", "host_cpu_s_per_mline.bulk",
        "transport_in_ms.triage", "transport_out_ms.triage",
        "engine_cpu_share.triage", "host_cpu_ms.triage",
    }
}
DEVICE_METRICS = {m["name"] for m in PER_LAYER if m["source"] == "device_trace"}


def test_every_stage_metric_is_declared():
    assert len(STAGE_METRICS) == 10


@pytest.mark.parametrize("cell", ["builtin83.bulk_unique", "builtin83.triage_open"])
def test_traced_rehearsal_reports_each_stage_metric(monkeypatch, tmp_path, capsys,
                                                    cell):
    tiny.steer(monkeypatch, tmp_path, run)
    assert run.main(["--workload", cell, "--seed", "3000000019",
                     "--seconds", "2", "--trace", "1"]) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True
    metrics = result["metrics"]
    expected = [n for n, cells in STAGE_METRICS.items() if cell in cells]
    assert expected
    for name in expected:
        assert name in metrics, name
        assert math.isfinite(metrics[name]["value"]), name
        assert metrics[name]["value"] >= 0, name
    for name in expected:
        if "cpu_share" in name:
            assert 0 < metrics[name]["value"] <= 1.05, name
    assert not DEVICE_METRICS & set(metrics)


@pytest.mark.parametrize("name", sorted(STAGE_METRICS))
def test_a_program_without_the_spans_reads_nothing(name):
    window = [{"status": 200, "lines": 1000, "sent": 0.0, "done": 0.5,
               "due": 0.0, "warmup": False}]
    phase = (("phase", "finalize"), ("route", "device"), ("tenant", "default"))
    scrape = {("logparser_phase_seconds_sum", phase): 1.0}
    r = Run(1.0, 1.0, window, dict(scrape), {k: v * 2 for k, v in scrape.items()})
    assert metric_reader(name)(r) is None


@pytest.mark.parametrize("name", ["host_cpu_s_per_mline.bulk", "host_cpu_ms.triage"])
def test_host_cpu_reads_the_serving_threads_not_the_process(name):
    """The harness serves from its own process, so the process's CPU
    holds the harness's work too; the readers take the serving threads'."""
    window = [{"status": 200, "lines": 500_000, "sent": 0.0, "done": 0.5,
               "due": 0.0, "warmup": False}] * 2
    served = ("logparser_request_cpu_seconds_total",
              (("route", "device"), ("tenant", "default")))
    process = ("logparser_process_cpu_seconds_total", ())
    before = {served: 1.0, process: 10.0}
    after = {served: 3.0, process: 50.0}
    got = metric_reader(name)(Run(1.0, 1.0, window, before, after))
    # 2 s over 1 Mline in two requests
    assert got == pytest.approx(1000.0 if name.endswith("triage") else 2.0)
