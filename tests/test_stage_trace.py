"""Stage spans, phase CPU and profiler annotations on the served /parse
path: what ``logparser_stage_seconds``, ``logparser_phase_cpu_seconds_total``,
``logparser_request_cpu_seconds_total`` and
``logparser_process_cpu_seconds_total`` record for one request, that
the phase labels stay as they were, that a ``jax.profiler`` trace names
the host's work by phase and stage, and that the device programs carry
their ``jax.named_scope`` names."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.ops.match import TIER_SCOPES
from log_parser_tpu.runtime import AnalysisEngine
from log_parser_tpu.serve import make_server

from helpers import make_pattern, make_pattern_set

TRANSPORT_STAGES = ("transport.read", "transport.decode", "transport.admission",
                    "transport.encode", "transport.write")
DEVICE_STAGES = ("device.upload", "device.launch", "device.wait",
                 "device.readback")

# the phase labels each serving mode gives logparser_phase_seconds
PHASES = {
    "pipelined": {"ingest", "overrides", "device", "verify", "lock_wait",
                  "finalize", "assemble"},
    "line_cache": {"ingest", "overrides", "cache", "device", "extract",
                   "verify", "lock_wait", "finalize", "assemble"},
    "batched": {"ingest", "overrides", "batch_wait", "device", "verify",
                "lock_wait", "finalize", "assemble"},
}

LOGS = "\n".join(
    ["INFO boot"] * 5
    + ["GC overhead limit exceeded", "java.lang.OutOfMemoryError: heap"]
    + [f"INFO tick {i}" for i in range(20)]
    + ["ERROR connection refused"]
)


def _engine(mode: str) -> AnalysisEngine:
    patterns = [
        make_pattern("oom", regex="OutOfMemoryError", confidence=0.9,
                     severity="CRITICAL", context=(1, 1),
                     secondaries=[("GC overhead", 0.6, 10)]),
        make_pattern("err", regex=r"\bERROR\b", confidence=0.5, severity="LOW"),
        make_pattern("refused", regex=r"connection (refused|reset)",
                     confidence=0.7, severity="HIGH"),
    ]
    engine = AnalysisEngine([make_pattern_set(patterns, "lib")], ScoringConfig())
    if mode == "line_cache":
        engine.enable_line_cache(8)
    elif mode == "batched":
        engine.enable_batching(wait_ms=1.0, batch_max=4)
    return engine


class _Served:
    def __init__(self, mode: str):
        self.mode = mode
        self.engine = _engine(mode)
        self.server = make_server(self.engine, host="127.0.0.1", port=0)
        self.url = f"http://127.0.0.1:{self.server.server_address[1]}"
        self.thread = threading.Thread(target=self.server.serve_forever,
                                       daemon=True)
        self.thread.start()

    def post(self, logs: str = LOGS) -> dict:
        body = json.dumps({"pod": {"metadata": {"name": "web-1"}},
                           "logs": logs}).encode()
        req = urllib.request.Request(self.url + "/parse", data=body,
                                     headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.status == 200
            return json.loads(resp.read())

    def stage_count(self, stage: str) -> int:
        return self.engine.obs.stage_seconds.snapshot(
            tenant="default", stage=stage)[2]

    def wait_for_transport(self, n: int) -> None:
        """The handler observes its stages after the response is written,
        so the client can read the answer first."""
        deadline = time.monotonic() + 10
        while self.stage_count("transport.write") < n:
            assert time.monotonic() < deadline, "transport stages never observed"
            time.sleep(0.01)

    def close(self) -> None:
        self.server.shutdown()
        self.server.server_close()
        self.thread.join(timeout=30)
        assert not self.thread.is_alive()
        if self.engine.batcher is not None:
            self.engine.batcher.close()


@pytest.fixture
def served(request):
    s = _Served(request.param)
    yield s
    s.close()


@pytest.mark.parametrize("served", sorted(PHASES), indirect=True)
def test_one_request_records_each_stage_once(served):
    mode = served.mode
    served.post()  # warm: the first request compiles its shapes
    served.wait_for_transport(1)
    obs = served.engine.obs
    before = {s: served.stage_count(s) for s in TRANSPORT_STAGES + DEVICE_STAGES}
    # one line the line cache has not seen, so every mode reaches the device
    served.post(LOGS + "\nINFO a line the cache has not seen")
    served.wait_for_transport(2)

    for stage in TRANSPORT_STAGES + DEVICE_STAGES:
        assert served.stage_count(stage) - before[stage] == 1, stage
    assert served.stage_count("engine.frequency") == 2

    route = "batched" if mode == "batched" else "device"
    labels = {key[1] for key, _ in obs.phase_seconds.series()}
    assert labels == PHASES[mode]

    # the second request alone: phase wall and CPU from its ring entry
    # and the counters' growth since the first
    trace = served.engine.last_trace
    phases = trace.as_dict()
    assert set(phases) == PHASES[mode]
    for phase, cpu in trace.cpu_dict().items():
        assert cpu <= phases[phase] + 0.005, phase
        assert obs.phase_cpu.value(tenant="default", phase=phase,
                                   route=route) > 0 or cpu == 0
    # phases added by hand (a batch's shared wait and device step) ran
    # on no thread of their own
    if mode == "batched":
        assert "batch_wait" not in trace.cpu_dict()
        assert "device" not in trace.cpu_dict()

    stages = trace.stage_dict()
    assert sum(stages[s] for s in DEVICE_STAGES) <= phases["device"]
    transport = {
        s: obs.stage_seconds.snapshot(tenant="default", stage=s)[1]
        for s in TRANSPORT_STAGES if s != "transport.write"
    }
    walls = obs.request_seconds.snapshot(route=route)[1]
    engine_s = sum(
        obs.phase_seconds.snapshot(tenant="default", phase=p, route=route)[1]
        for p in PHASES[mode]
    )
    frequency_s = obs.stage_seconds.snapshot(
        tenant="default", stage="engine.frequency")[1]
    assert sum(transport.values()) + frequency_s + engine_s <= walls

    # the serving threads' CPU holds every phase's CPU of the handler
    # thread, and stays inside the handlers' wall
    served_cpu = obs.request_cpu.value(tenant="default", route=route)
    assert 0 < served_cpu <= walls + 0.01
    if mode != "batched":
        phase_cpu = sum(obs.phase_cpu.value(tenant="default", phase=p,
                                            route=route) for p in PHASES[mode])
        assert phase_cpu <= served_cpu

    assert obs.registry.collected_value(
        "logparser_process_cpu_seconds_total") > 0
    text = obs.registry.render()
    assert 'logparser_stage_seconds_count{tenant="default",stage="device.wait"}' in text
    assert "# TYPE logparser_phase_cpu_seconds_total counter" in text


@pytest.mark.parametrize("served", ["line_cache"], indirect=True)
def test_profile_names_the_hosts_work_inside_the_request(served, tmp_path):
    from jax.profiler import ProfileData

    served.post()  # compile outside the trace
    served.wait_for_transport(1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.request"):
            served.post(LOGS + "\nINFO a line the cache has not seen")
    finally:
        jax.profiler.stop_trace()
    paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    assert paths
    pd = ProfileData.from_file(paths[0])
    events: dict[str, list[tuple[int, int]]] = {}
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                events.setdefault(e.name, []).append(
                    (int(e.start_ns), int(e.end_ns)))
    (lo, hi), = events["test.request"]
    for name in ("engine.extract", "transport.decode", "device.wait",
                 "engine.device", "engine.finalize"):
        spans = events.get(name)
        assert spans, name
        assert any(lo <= s and e <= hi for s, e in spans), name


def test_cube_program_names_itself_and_each_populated_tier():
    engine = _engine("pipelined")
    fused = engine.fused
    m = fused.matchers
    populated = {
        "dense": bool(m.dfa_cols),
        "shiftor": m.shiftor is not None,
        "bitglush": m.bitglush is not None,
        "union": bool(m.multi_groups),
        "prefilter": m.prefilter is not None,
    }
    assert any(populated.values())
    lines = np.zeros((8, 32), dtype=np.uint8)
    lens = np.zeros((8,), dtype=np.int32)
    n = np.int32(8)
    cube = fused._jit_cube_plain.lower(lines, lens, n).as_text(debug_info=True)
    assert "logparser.cube" in cube
    for tier, on in populated.items():
        assert (TIER_SCOPES[tier] in cube) == on, tier
    step = fused._jit_plain.lower(64, lines, lens, n).as_text(debug_info=True)
    assert "logparser.cube" in step and "logparser.extract" in step
    # the module carries the program's name, not a lambda's
    assert "jit_logparser_cube" in fused._jit_cube_plain.lower(
        lines, lens, n).as_text()


def test_serving_a_new_shape_starts_no_background_thread():
    engine = _engine("line_cache")
    before = {t.ident for t in threading.enumerate()}
    from log_parser_tpu.models.pod import PodFailureData

    for logs in (LOGS, "\n".join([LOGS] * 40)):  # two row buckets
        engine.analyze(PodFailureData(pod={"metadata": {"name": "p"}},
                                      logs=logs))
    new = [t.name for t in threading.enumerate() if t.ident not in before]
    assert "dispatch-cost" not in new
    assert not [t for t in threading.enumerate() if t.name == "dispatch-cost"]


def test_annotations_import_no_jax():
    code = (
        "import sys\n"
        "from log_parser_tpu.utils.trace import PhaseTrace, NO_TRACE\n"
        "t = PhaseTrace()\n"
        "with t.phase('ingest'):\n    pass\n"
        "with t.stage('transport.read'):\n    pass\n"
        "with NO_TRACE.stage('device.wait'):\n    pass\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "assert set(t.as_dict()) == {'ingest'} and set(t.cpu_dict()) == {'ingest'}\n"
        "assert set(t.stage_dict()) == {'transport.read'}\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", code], cwd=root,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
