"""Multi-process (DCN) scale-out: a 2-process CPU mesh running the sharded
engine in lockstep (parallel/distributed.py; SURVEY.md §2.2/§5.8 — the
reference has no inter-process story at all; this is the jax.distributed
equivalent of scaling past one host).

The test spawns two fresh Python processes (4 virtual CPU devices each →
one 8-device global mesh), has the coordinator broadcast two requests
through DistributedShardedEngine, and asserts the coordinator's scores
match the single-process GoldenAnalyzer exactly. The subprocess boundary
is real: collectives ride the distributed runtime (Gloo), not shared
memory.
"""

from __future__ import annotations

import json
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

# some jaxlib builds (e.g. 0.4.x) have no multi-process collective support
# on the CPU backend at all — then the 2-process harness cannot run here
# and the stubbed single-process coverage in test_resilience.py carries
# the dispatch/degrade logic instead
_NO_CPU_MULTIPROCESS = "Multiprocess computations aren't implemented"


def _skip_if_unsupported(outs):
    if any(_NO_CPU_MULTIPROCESS in out for out in outs):
        pytest.skip("CPU backend lacks multi-process collectives")

_WORKER = textwrap.dedent(
    """
    import json, os, sys

    pid = int(sys.argv[1])
    port = sys.argv[2]
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["LOG_PARSER_TPU_NO_FALLBACK"] = "1"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from log_parser_tpu.parallel.distributed import (
        DistributedShardedEngine,
        init_distributed,
    )

    init_distributed(f"127.0.0.1:{port}", num_processes=2, process_id=pid)
    assert jax.device_count() == 8, jax.device_count()
    assert jax.local_device_count() == 4

    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.models.pattern import (
        ContextExtraction, Pattern, PatternSet, PatternSetMetadata,
        PrimaryPattern, SecondaryPattern,
    )
    from log_parser_tpu.parallel import make_mesh

    sets = [PatternSet(
        metadata=PatternSetMetadata(library_id="dist-lib", name="dist"),
        patterns=[
            Pattern(
                id="oom", name="oom", severity="HIGH",
                primary_pattern=PrimaryPattern(regex="OutOfMemoryError", confidence=0.8),
                secondary_patterns=[SecondaryPattern(
                    regex="GC overhead", weight=0.6, proximity_window=10)],
                context_extraction=ContextExtraction(lines_before=2, lines_after=1),
            ),
            Pattern(
                id="conn", name="conn", severity="MEDIUM",
                primary_pattern=PrimaryPattern(regex="Connection refused", confidence=0.7),
            ),
        ],
    )]

    engine = DistributedShardedEngine(sets, ScoringConfig(), mesh=make_mesh())

    logs = "\\n".join(
        "GC overhead limit" if i == 17
        else "java.lang.OutOfMemoryError: heap" if i == 20
        else "dial tcp: Connection refused" if i in (3, 44)
        else f"INFO tick {i}"
        for i in range(64)
    )
    data = PodFailureData(pod={"metadata": {"name": "dist"}}, logs=logs)

    if pid == 0:
        r1 = engine.analyze(data)
        r2 = engine.analyze(data)  # second batch: frequency state advanced
        engine.shutdown_followers()
        print("RESULT " + json.dumps({
            "scores1": [e.score for e in r1.events],
            "lines1": [e.line_number for e in r1.events],
            "ids1": [e.matched_pattern.id for e in r1.events],
            "scores2": [e.score for e in r2.events],
        }), flush=True)
    else:
        engine.follower_loop()
        print("FOLLOWER_DONE", flush=True)
    """
)


def test_two_process_mesh_matches_golden():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, str(pid), str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=REPO,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    _skip_if_unsupported(outs)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {i} failed:\n{out[-3000:]}"
    assert "FOLLOWER_DONE" in outs[1], outs[1][-2000:]

    result = json.loads(outs[0].split("RESULT ", 1)[1].splitlines()[0])

    # golden single-process truth for the same two-batch request stream
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.golden import GoldenAnalyzer
    from log_parser_tpu.models import PodFailureData

    from helpers import make_pattern, make_pattern_set

    sets = [
        make_pattern_set(
            [
                make_pattern(
                    "oom", regex="OutOfMemoryError", confidence=0.8,
                    severity="HIGH", secondaries=[("GC overhead", 0.6, 10)],
                    context=(2, 1),
                ),
                make_pattern(
                    "conn", regex="Connection refused", confidence=0.7,
                    severity="MEDIUM",
                ),
            ],
            library_id="dist-lib",
        )
    ]
    logs = "\n".join(
        "GC overhead limit" if i == 17
        else "java.lang.OutOfMemoryError: heap" if i == 20
        else "dial tcp: Connection refused" if i in (3, 44)
        else f"INFO tick {i}"
        for i in range(64)
    )
    golden = GoldenAnalyzer(sets, ScoringConfig())
    data = PodFailureData(pod={"metadata": {"name": "dist"}}, logs=logs)
    g1 = golden.analyze(data)
    g2 = golden.analyze(data)

    assert result["ids1"] == [e.matched_pattern.id for e in g1.events]
    assert result["lines1"] == [e.line_number for e in g1.events]
    assert result["scores1"] == [e.score for e in g1.events]
    assert result["scores2"] == [e.score for e in g2.events]


_CHAOS_WORKER = textwrap.dedent(
    """
    import json, os, sys

    pid = int(sys.argv[1])
    port = sys.argv[2]
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["LOG_PARSER_TPU_NO_FALLBACK"] = "1"
    if pid == 0:
        # a follower stalls every dispatch after the first request; the
        # bounded broadcast (2s x 2 attempts) must flip the coordinator to
        # degrade-to-local instead of deadlocking
        os.environ["LOG_PARSER_TPU_FAULTS"] = "follower_hang:30@after=1"
        os.environ["LOG_PARSER_TPU_BROADCAST_TIMEOUT_S"] = "2"
        os.environ["LOG_PARSER_TPU_BROADCAST_RETRIES"] = "1"
        os.environ["LOG_PARSER_TPU_BROADCAST_BACKOFF_S"] = "0.05"
        os.environ["LOG_PARSER_TPU_DEAD_AFTER"] = "2"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from log_parser_tpu.parallel.distributed import (
        DistributedShardedEngine,
        init_distributed,
    )

    init_distributed(f"127.0.0.1:{port}", num_processes=2, process_id=pid)

    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.models.pattern import (
        Pattern, PatternSet, PatternSetMetadata, PrimaryPattern,
    )
    from log_parser_tpu.parallel import make_mesh
    from log_parser_tpu.runtime import faults

    faults.ensure_env()
    sets = [PatternSet(
        metadata=PatternSetMetadata(library_id="chaos-lib", name="chaos"),
        patterns=[
            Pattern(
                id="oom", name="oom", severity="HIGH",
                primary_pattern=PrimaryPattern(
                    regex="OutOfMemoryError", confidence=0.8),
            ),
            Pattern(
                id="conn", name="conn", severity="MEDIUM",
                primary_pattern=PrimaryPattern(
                    regex="Connection refused", confidence=0.7),
            ),
        ],
    )]
    engine = DistributedShardedEngine(sets, ScoringConfig(), mesh=make_mesh())

    logs = "\\n".join(
        "java.lang.OutOfMemoryError: heap" if i == 20
        else "dial tcp: Connection refused" if i in (3, 44)
        else f"INFO tick {i}"
        for i in range(64)
    )
    data = PodFailureData(pod={"metadata": {"name": "chaos"}}, logs=logs)

    if pid == 0:
        # r1 dispatches cleanly; r2 exhausts the retry budget against the
        # hang and flips degraded; r3 serves inside the degraded window
        results = [engine.analyze(data) for _ in range(3)]
        faults.active().lift()  # the "follower" recovers
        probed = engine.probe_mesh()
        results.append(engine.analyze(data))  # back on the full mesh
        stats = engine.mesh_health.stats()
        engine.shutdown_followers()
        print("RESULT " + json.dumps({
            "degraded": [
                r.metadata.degraded if r.metadata else None for r in results
            ],
            "ids": [[e.matched_pattern.id for e in r.events] for r in results],
            "lines": [[e.line_number for e in r.events] for r in results],
            "probed": probed,
            "mode": stats["mode"],
            "timeouts": stats["broadcastTimeouts"],
            "degradedRequests": stats["degradedRequests"],
            "readmissions": stats["readmissions"],
        }), flush=True)
    else:
        engine.follower_loop()
        print("FOLLOWER_DONE", flush=True)
    """
)


@pytest.mark.slow
@pytest.mark.chaos
def test_follower_hang_degrades_to_local_then_readmits():
    """ISSUE 2 acceptance: with a seeded follower hang every request still
    completes — the degraded window is visible in response metadata, the
    probe re-admits the mesh, and the group shuts down cleanly."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _CHAOS_WORKER, str(pid), str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=REPO,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    _skip_if_unsupported(outs)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {i} failed:\n{out[-3000:]}"
    assert "FOLLOWER_DONE" in outs[1], outs[1][-2000:]

    result = json.loads(outs[0].split("RESULT ", 1)[1].splitlines()[0])
    marker = "distributed-fallback"
    assert result["degraded"] == [None, marker, marker, None]
    # every request found the same events regardless of serving path
    assert all(ids == result["ids"][0] for ids in result["ids"][1:])
    assert all(ln == result["lines"][0] for ln in result["lines"][1:])
    assert sorted(result["ids"][0]) == ["conn", "conn", "oom"]
    assert result["probed"] is True
    assert result["mode"] == "distributed"  # re-admitted before shutdown
    assert result["timeouts"] == 2  # r2: initial attempt + one retry
    assert result["degradedRequests"] == 2
    assert result["readmissions"] == 1
