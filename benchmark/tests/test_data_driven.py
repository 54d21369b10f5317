"""A configuration, a traffic mix, an arrival process, a line source and
a metric are added as new files plus new entries in ``BENCHMARK.json``,
in a copy of the checkout, and the harness finds and runs them by name
with no edit to a file it had. A configuration that asks for serve's
sharded engine or its micro-batcher is served the same way."""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.cell import ROOT

LIBRARY = """\
metadata:
  library_id: throwaway
patterns:
  - id: tw-oom
    severity: CRITICAL
    primary_pattern: {regex: "OutOfMemoryError", confidence: 0.9}
    context_extraction: {lines_before: 2, lines_after: 1}
  - id: tw-refused
    severity: HIGH
    primary_pattern: {regex: "Connection refused|connect timed out", confidence: 0.7}
"""

# a new arrival process: bursts of requests due together
BURST = '''\
def plan(loop, sizes, seed, seconds):
    every, per = float(loop["every_s"]), int(loop["burst"])
    n_bursts = max(1, int(seconds // every))
    n = n_bursts * per
    due = [b * every for b in range(n_bursts) for _ in range(per)]
    return due, sizes([(j + 0.5) / n for j in range(n)])
'''

# a new line source: a format with the request's tag and the position
STAMPED = '''\
def lines(fmt, rng, positions, ctx):
    return [fmt.format(i=i, tag=ctx["tag"]) for i in positions]
'''

STEER = """\
import json, pathlib, sys, tempfile
sys.path.insert(0, {root!r})
from benchmark import run
from benchmark.tests import tiny

class Patch:
    setattr = staticmethod(setattr)

def main():
    tiny.steer(Patch(), pathlib.Path(tempfile.mkdtemp()), run, lambda c: c)
    sys.exit(run.main(["--workload", {cell!r}, "--seed", "77",
                       "--seconds", "2", "--trace", "0"]))

if __name__ == "__main__":
    main()
"""


def _digests(root: str) -> dict:
    out = {}
    for base, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        if "__pycache__" in base:
            continue
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def _checkout(tmp_path):
    copy = tmp_path / "checkout"
    copy.mkdir()
    skip = shutil.ignore_patterns("__pycache__")
    for part in ("benchmark", "log_parser_tpu", "native"):
        shutil.copytree(os.path.join(ROOT, part), copy / part, ignore=skip)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy / "BENCHMARK.json")
    return copy


def _run(copy, cell: str, **env) -> dict:
    (copy / "steer.py").write_text(STEER.format(root=str(copy), cell=cell))
    env = dict(os.environ, JAX_PLATFORMS="cpu", LOG_PARSER_TPU_XLA_CACHE="0", **env)
    r = subprocess.run([sys.executable, "steer.py"], cwd=copy, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, r.stderr[-3000:]
    return result


def _add(copy, config=None, workload=None, end_to_end=None, reports=()) -> None:
    """New entries, and the new cell named among those that report each
    of the end-to-end metrics ``reports``."""
    manifest = json.loads((copy / "BENCHMARK.json").read_text())
    for key, entry in (("configs", config), ("workloads", workload),
                       ("end_to_end", end_to_end)):
        if entry:
            manifest[key].append(entry)
    for m in manifest["end_to_end"]:
        if m["name"] in reports:
            m["workloads"].append(workload["name"])
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))


def test_new_config_mix_arrivals_source_and_metric_are_found_by_name(tmp_path):
    copy = _checkout(tmp_path)
    before = _digests(str(copy))

    bench = copy / "benchmark"
    (bench / "configs" / "throwaway").mkdir()
    (bench / "configs" / "throwaway" / "lib.yaml").write_text(LIBRARY)
    config = json.loads((bench / "configs" / "builtin83.json").read_text())
    config.update(name="throwaway", library={
        "kind": "directory", "path": "benchmark/configs/throwaway"})
    (bench / "configs" / "throwaway.json").write_text(json.dumps(config))
    (bench / "traffic" / "arrivals" / "burst.py").write_text(BURST)
    (bench / "traffic" / "sources" / "stamped.py").write_text(STAMPED)
    (bench / "traffic" / "mini.json").write_text(json.dumps({
        "name": "mini",
        "loop": {"kind": "open", "arrivals": "burst", "every_s": 0.5, "burst": 3},
        "lines": {"fixed": 400},
        "mix": [
            {"weight": 30, "stamped": "INFO mini {tag}.{i}"},
            {"weight": 1, "pick": ["java.lang.OutOfMemoryError: heap",
                                   "dial tcp: Connection refused"]},
        ],
        "warmup": {"requests": 1},
    }))
    (bench / "metrics" / "answers_per_s.py").write_text(
        "def read(run):\n"
        "    return len(run.answered) / run.seconds\n"
    )
    _add(copy,
         config={"name": "throwaway", "source": "a test's own library",
                 "file": "benchmark/configs/throwaway.json", "reduced": [],
                 "why": "shows a configuration is found by name"},
         workload={"name": "throwaway.mini", "config": "throwaway",
                   "traffic": "mini", "chips": 1,
                   "why": "shows a traffic mix is found by name"},
         end_to_end={"name": "answers_per_s", "unit": "1/s", "better": "higher",
                      "bound": 0.1, "source": "host_clock",
                      "workloads": ["throwaway.mini"]})

    result = _run(copy, "throwaway.mini")
    # four bursts of three requests in a two-second window
    assert result["attempted"] == 12
    assert result["failed"] == 0
    assert result["metrics"]["answers_per_s"]["value"] == 6.0
    assert set(result["metrics"]) == {"answers_per_s", "setup_s"}

    after = _digests(str(copy))
    assert {k: after[k] for k in before} == before


@pytest.mark.parametrize("serve,chips", [
    ({"sharded": True, "line_cache_mb": 0}, 4),
    ({"batching": "on"}, 1),
], ids=["sharded", "batching"])
def test_serve_keys_build_the_engine_serve_builds(tmp_path, serve, chips):
    """A configuration asks for serve's sharded engine (across four
    chips) or its micro-batcher by its ``serve`` keys alone."""
    copy = _checkout(tmp_path)
    before = _digests(str(copy))

    bench = copy / "benchmark"
    config = json.loads((bench / "configs" / "builtin83.json").read_text())
    config["name"] = "builtin83_variant"
    config["serve"] = dict(config["serve"], **serve)
    config["chips"] = chips
    (bench / "configs" / "builtin83_variant.json").write_text(json.dumps(config))
    mix = json.loads((bench / "traffic" / "bulk_unique.json").read_text())
    mix["lines"] = {"fixed": 3000}
    mix["loop"]["pool_per_s"] = 20.0
    (bench / "traffic" / "bulk_small.json").write_text(json.dumps(mix))
    _add(copy,
         config={"name": "builtin83_variant", "source": "a test's copy of builtin83",
                 "file": "benchmark/configs/builtin83_variant.json",
                 "reduced": [], "why": "shows serve's engine options are built"},
         workload={"name": "builtin83_variant.bulk_small",
                   "config": "builtin83_variant", "traffic": "bulk_small",
                   "chips": chips, "why": "the engine a serve key asks for"},
         reports=("lines_per_s",))

    result = _run(copy, "builtin83_variant.bulk_small",
                  XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    assert result["device"]["count"] == chips
    assert result["failed"] == 0
    assert result["metrics"]["lines_per_s"]["value"] > 0

    after = _digests(str(copy))
    assert {k: after[k] for k in before} == before
