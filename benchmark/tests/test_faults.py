"""The comparison that decides ``correct`` has to refuse a broken timed
path. Each test drives a whole steered run (as ``test_rehearsal``) with
one fault planted in the program underneath and sees ``correct`` come
out false; the control test puts the float32 reference in the program's
place. This cell runs on one chip, so it has no exchange between chips
to leave out."""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark import control
from benchmark.tests import tiny
from benchmark.tests.test_rehearsal import run_cell

CELL = "builtin83.bulk_unique"


def test_frequency_state_left_unchanged(monkeypatch, tmp_path, capsys):
    """The finish step never records its matches, so every request sees
    the frequency state the first one saw."""
    from log_parser_tpu.golden.engine import GoldenFrequencyTracker

    monkeypatch.setattr(GoldenFrequencyTracker, "record_pattern_matches",
                        lambda self, pid, n: None)
    result, _, _ = run_cell(monkeypatch, tmp_path, capsys, CELL)
    assert result["correct"] is False
    assert result["checks"]["max_score_delta"]["value"] > \
        result["checks"]["max_score_delta"]["limit"]


def test_half_the_batch_left_out(monkeypatch, tmp_path, capsys):
    """The device step drops the second half of every batch's rows."""
    from log_parser_tpu.ops.fused import FusedMatchScore

    real = FusedMatchScore.cube_rows

    def half(self, lines_u8, lengths, n_lines, *a, **k):
        out = np.array(real(self, lines_u8, lengths, n_lines, *a, **k))
        out[n_lines // 2:] = False
        return out

    monkeypatch.setattr(FusedMatchScore, "cube_rows", half)
    result, _, _ = run_cell(monkeypatch, tmp_path, capsys, CELL)
    assert result["correct"] is False
    assert result["checks"]["mismatched_requests"]["value"] > 0


def test_one_answer_altered_where_it_is_produced(monkeypatch, tmp_path, capsys):
    """Finalize nudges the first score of one request by 1e-7."""
    from log_parser_tpu.runtime import engine as engine_mod

    real = engine_mod.finalize_batch
    done = {"n": 0}

    def nudged(*a, **k):
        fin = real(*a, **k)
        if len(fin.scores) and not done["n"]:
            done["n"] += 1
            scores = np.array(fin.scores, dtype=np.float64)
            scores[0] += 1e-7
            fin = dataclasses.replace(fin, scores=scores)
        return fin

    monkeypatch.setattr(engine_mod, "finalize_batch", nudged)
    result, _, _ = run_cell(monkeypatch, tmp_path, capsys, CELL)
    assert done["n"] == 1
    assert result["correct"] is False
    assert result["checks"]["max_score_delta"]["value"] >= 1e-7 * 0.99


def test_control_float32_reference_is_refused():
    """The reference in float32, in the program's place, fails."""
    from benchmark.cell import load_cell

    for name in ("builtin83.bulk_unique", "synth10k.bulk_unique"):
        cell = tiny.shrink(load_cell(name))
        checks = control.control_checks(cell, seed=5000000011, n_window=6,
                                        seconds=2.0, workers=2)
        value, limit = checks["max_score_delta"]
        assert value > limit, (name, checks)
        assert checks["mismatched_requests"][0] == 0
