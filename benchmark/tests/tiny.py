"""Cells cut to a size a CPU test run can hold, and the steering of the
harness's platform check onto the CPU."""

from __future__ import annotations

import copy
import json

from benchmark import cell as cellmod


def shrink(cell: cellmod.Cell) -> cellmod.Cell:
    cell = copy.deepcopy(cell)
    t = cell.traffic
    if "fixed" in t["lines"]:
        t["lines"]["fixed"] = 1500
    else:
        t["lines"]["log_uniform"] = [60, 900]
        t["warmup"] = {"grid_per_octave": 4}
    if t["loop"]["kind"] == "open":
        t["loop"]["rate_per_s"] = 6.0
    else:
        t["loop"]["pool_per_s"] = 40.0
    lib = cell.config["library"]
    if lib["kind"] == "synth":
        lib["patterns"] = 120
    return cell


def steer(monkeypatch, tmp_path, run_module, transform=shrink) -> None:
    """Accept the CPU, and run every cell through ``transform``."""
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"devices": {"cpu": {}}}))
    monkeypatch.setattr(run_module, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(run_module, "PEAKS_FILE", str(peaks))
    real = run_module.load_cell
    monkeypatch.setattr(run_module, "load_cell",
                        lambda name: transform(real(name)))
