"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

A later change adds a configuration, a traffic mix or a metric as new
files plus new entries in ``BENCHMARK.json``; nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell in cells


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration and traffic files read,
    and the metrics it reports. Raises ``KeyError`` for an unknown cell."""
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"]),
              encoding="utf-8") as f:
        config = json.load(f)
    bench_dir = os.path.join(root, os.path.basename(BENCH_DIR))
    with open(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"),
              encoding="utf-8") as f:
        traffic = json.load(f)
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def metric_reader(metric: str, root: str = ROOT):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(root, os.path.basename(BENCH_DIR), "metrics",
                        metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric.replace(".", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
