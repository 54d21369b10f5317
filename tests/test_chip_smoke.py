"""chip_smoke.py rehearsed on the CPU (on-chip-measurement guide §2).

The smoke's platform check is steered here, never through an option of
the script: with ``REQUIRED_PLATFORM`` set to ``cpu`` its phases run end
to end at a tiny size, both Pallas kernels in interpret mode. Unsteered,
it must refuse the CPU and print no result.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


@pytest.fixture
def on_cpu(monkeypatch):
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "CONFIG2_LINES", 600)
    monkeypatch.setattr(chip_smoke, "SEEDED_LINES", 700)


def test_refuses_without_tpu_in_process(capsys):
    with pytest.raises(SystemExit) as exc_info:
        chip_smoke.main([])
    assert exc_info.value.code != 0
    assert '"ok"' not in capsys.readouterr().out


def test_refuses_without_tpu_as_a_user_runs_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=300, cwd=REPO, env=env,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs 'tpu'" in r.stderr


def test_one_chip_phases(on_cpu, capsys):
    assert chip_smoke.main(["--seed", "3"]) == 0
    out = capsys.readouterr().out
    assert _last_json(out) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 8},
    }
    for line in ("served: config1-pod-failure", "served: seeded-700",
                 "kernel-bitglush: traced=",
                 "kernel-union-dfa: layout=off-path reason=", "native: ",
                 "compileCache: "):
        assert line in out, line


def test_four_chip_phase(on_cpu, capsys):
    """conftest's 8 virtual CPU devices stand in for the 4-chip host."""
    assert chip_smoke.main(["--chips", "4"]) == 0
    out = capsys.readouterr().out
    assert "sharded-mesh4: output devices=4" in out
    assert "block devices=4" in out
    assert _last_json(out)["ok"] is True


def test_seeded_log_is_reproducible_and_shaped():
    a = chip_smoke.seeded_log(5000, 7)
    assert a == chip_smoke.seeded_log(5000, 7)
    assert a != chip_smoke.seeded_log(5000, 8)
    lines = a.split("\n")
    assert len(lines) == 5000
    # the benchmark corpus's density: six failure kinds in every 997 lines
    failures = sum("INFO reconcile tick" not in line for line in lines)
    assert 5 <= failures <= 60


def test_corpus_shapes_unchanged():
    """bench's config-2 corpus keeps its fixed layout, and the seeded draw
    uses the same six failure lines."""
    from log_parser_tpu.utils import corpus

    lines = bench_corpus = corpus.pod_log(2000).split("\n")
    assert len(lines) == 2000
    assert lines[5] == lines[5 + corpus.PERIOD] == corpus.SPECIALS[5]
    assert lines[4] == "2026-07-29T07:04:04Z INFO reconcile tick 4 status=ok"
    seeded = set(chip_smoke.seeded_log(20_000, 1).split("\n"))
    assert set(corpus.SPECIALS.values()) <= seeded
    assert set(corpus.SPECIALS.values()) <= set(bench_corpus)


def test_compare_bites():
    """The smoke's parity check fails on a missing event and on a score
    off by more than 1e-6, and passes within it."""
    want = {"events": [
        {"lineNumber": 3, "matchedPattern": {"id": "a"}, "score": 1.5},
        {"lineNumber": 9, "matchedPattern": {"id": "b"}, "score": 2.0},
    ]}
    close = {"events": [dict(e, score=e["score"] + 5e-7)
                        for e in want["events"]]}
    assert chip_smoke.compare("close", close, want) == pytest.approx(5e-7)
    with pytest.raises(AssertionError, match="events differ"):
        chip_smoke.compare("missing", {"events": want["events"][:1]}, want)
    far = {"events": [dict(e, score=e["score"] + 1e-5)
                      for e in want["events"]]}
    with pytest.raises(AssertionError, match="score delta"):
        chip_smoke.compare("far", far, want)
