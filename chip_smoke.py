"""Bring-up smoke: the served ``POST /parse`` path on the chip, end to end.

One process, no child process: a chip belongs to the process that first
touches JAX. Run it through the chip tool; on a host without a TPU it
exits non-zero and prints no result.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # only the multi-chip engines, 4 chips

One chip:

1. served: the engine ``python -m log_parser_tpu.serve --pattern-dir
   log_parser_tpu/patterns/builtin`` builds (83 patterns, 64 MB line
   cache), behind the real HTTP server with the golden fallback off.
   Three POSTs — a short pod-failure log (BASELINE config 1), the
   10k-line synthetic pod log (config 2, ``utils.corpus.pod_log``) and a
   200k-line log drawn from ``--seed`` in the same shape — each checked
   against ``golden.GoldenAnalyzer`` replayed in order on a fresh
   instance: the same ``(line, pattern)`` events, every score within
   1e-6. ``GET /trace/last`` must show no fallback and no host-routed
   request, and the native library must have loaded.
2. kernels: the same requests through engines with the Pallas kernels
   on (``LOG_PARSER_TPU_PALLAS=1``, ``LOG_PARSER_TPU_PALLAS_DFA=1``). The
   builtin bank's dense columns ride the bit tier, so the bitglush
   kernel runs in the served layout. The union tier only packs
   groups with the bit tier off, a layout no deployment builds and
   ``serve`` cannot reach: the union-DFA kernel is proved in a second
   engine whose matchers are swapped for ``bitglush_max_words=0`` before
   its fused program exists, and its output lines say ``layout=off-path``.
   Each kernel must have run (traced into the served program / ``kernel``
   block ``kernelBatches > 0`` with an admitted reason) and the events
   must match golden.

Four chips (``--chips 4``): the seeded request through ``ShardedEngine``
on a 4-device mesh (served, as ``serve --sharded`` does) and through
``PatternShardedEngine`` with 4 blocks, each compared with the one-chip
engine and golden, and each required to have put its outputs on 4
devices.

Earlier lines report the device, per-request wall times, compile
seconds, the compile cache and the native library. The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed
check raises and exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
PATTERN_DIR = os.path.join(REPO, "log_parser_tpu", "patterns", "builtin")
# what jax.devices()[0].platform must be: a CPU rehearsal
# (tests/test_chip_smoke.py) steers it
REQUIRED_PLATFORM = "tpu"
SCORE_TOL = 1e-6
CONFIG2_LINES = 10_000
SEEDED_LINES = 200_000

# BASELINE config 1: one pod-failure log as a podmortem operator posts it
POD_FAILURE_LOG = "\n".join([
    "2026-07-29T07:00:01Z INFO Starting OrderService v2.3.1 on pod "
    "orders-7f9c4b-x2k",
    "2026-07-29T07:00:02Z INFO Connecting to postgres at 10.0.0.7:5432",
    "2026-07-29T07:00:04Z WARN Connection attempt 1 failed, retrying",
    "2026-07-29T07:00:06Z ERROR dial tcp 10.0.0.7:5432: Connection refused",
    "2026-07-29T07:00:09Z INFO Connected to postgres",
    "2026-07-29T07:05:12Z INFO Processed 10000 orders",
    "[Full GC (Ergonomics) 255M->250M(256M), 0.41 secs]",
    "[Full GC (Ergonomics) 255M->253M(256M), 0.52 secs]",
    "2026-07-29T07:05:40Z ERROR request failed with IllegalStateException",
    "Exception in thread \"main\" java.lang.OutOfMemoryError: Java heap "
    "space",
    "    at java.util.Arrays.copyOf(Arrays.java:3332)",
    "    at com.example.orders.Batch.collect(Batch.java:88)",
    "    at com.example.orders.Service.handle(Service.java:42)",
    "Warning: Liveness probe failed: HTTP probe failed with statuscode: "
    "503",
    "Back-off restarting failed container orders in pod "
    "orders-7f9c4b-x2k",
])


def say(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def seeded_log(n: int, seed: int) -> str:
    """``n`` lines in the benchmark's shape (``utils.corpus``), with
    positions and timestamps drawn from ``seed``."""
    import numpy as np

    from log_parser_tpu.utils import corpus

    return corpus.pod_log(n, np.random.default_rng(seed))


class CompileClock:
    """Seconds JAX spent in backend compiles (cache retrievals included),
    read from its own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def device_or_exit():
    """The device check comes before anything else is built."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != REQUIRED_PLATFORM:
        print(
            f"chip_smoke: JAX found {len(devices)} {d.platform!r} device(s), "
            f"needs {REQUIRED_PLATFORM!r}",
            file=sys.stderr,
        )
        sys.exit(2)
    say(f"device: {d.platform} {d.device_kind} x{len(devices)}")
    return devices


def load_sets():
    from log_parser_tpu.patterns import load_pattern_directory

    sets = load_pattern_directory(PATTERN_DIR)
    n = sum(len(s.patterns or []) for s in sets)
    say(f"patterns: {n} from {os.path.relpath(PATTERN_DIR, REPO)}")
    return sets


def build_engine(sets, *, pallas: bool = False, bit_words: int | None = None,
                 sharded_mesh=None):
    """The engine as ``log_parser_tpu.serve`` builds it: config from the
    environment, the 64 MB line cache on the single-device engine, the
    golden fallback off. Its matchers are built here, while the Pallas
    switches are set (they are read once, at construction)."""
    import dataclasses

    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.ops.match import MatcherBanks
    from log_parser_tpu.runtime import AnalysisEngine

    config = dataclasses.replace(
        ScoringConfig.from_env(), pattern_directory=PATTERN_DIR
    )
    flags = {"LOG_PARSER_TPU_PALLAS": "1", "LOG_PARSER_TPU_PALLAS_DFA": "1"}
    saved = {k: os.environ.get(k) for k in flags}
    if pallas:
        os.environ.update(flags)
    try:
        if sharded_mesh is not None:
            from log_parser_tpu.parallel import ShardedEngine

            engine = ShardedEngine(sets, config, mesh=sharded_mesh)
        else:
            engine = AnalysisEngine(sets, config)
            if bit_words is not None:
                # the fused program captures the matchers when it is
                # built: swapping them later would keep the old layout
                check(engine._fused is None,
                      "fused program built before the matcher swap")
                engine._matchers = MatcherBanks(
                    engine.bank, bitglush_max_words=bit_words
                )
            engine.fused  # noqa: B018 - builds the matchers now
            engine.enable_line_cache(64)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    engine.fallback_to_golden = False
    return engine


class Served:
    """``engine`` behind the real HTTP server, on a thread."""

    def __init__(self, engine):
        from log_parser_tpu.serve.http import make_server

        self.server = make_server(engine, "127.0.0.1", 0)
        if getattr(engine, "mesh", None) is not None:
            self.server.stream_enabled = False  # as serve --sharded does
        self.url = f"http://127.0.0.1:{self.server.server_address[1]}"
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True
        )
        self.thread.start()

    def post(self, name: str, logs: str) -> tuple[dict, float]:
        body = json.dumps(
            {"pod": {"metadata": {"name": name}}, "logs": logs}
        ).encode()
        req = urllib.request.Request(
            self.url + "/parse", data=body,
            headers={"Content-Type": "application/json"},
        )
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=900) as resp:
            check(resp.status == 200, f"{name}: HTTP {resp.status}")
            doc = json.loads(resp.read())
        return doc, time.perf_counter() - t0

    def trace(self) -> dict:
        with urllib.request.urlopen(self.url + "/trace/last", timeout=60) as r:
            return json.loads(r.read())

    def close(self) -> None:
        self.server.shutdown()
        self.server.server_close()
        self.thread.join(timeout=30)


def events_of(result) -> list[tuple[int, str, float]]:
    """(line, pattern id, score) of a response body or an AnalysisResult."""
    if isinstance(result, dict):
        return [
            (e["lineNumber"], e["matchedPattern"]["id"], e["score"])
            for e in result.get("events") or []
        ]
    return [
        (e.line_number, e.matched_pattern.id, e.score)
        for e in result.events or []
    ]


def compare(name: str, got, want) -> float:
    """Same (line, pattern) events in the same order, every score within
    SCORE_TOL; returns the largest score difference."""
    g, w = events_of(got), events_of(want)
    check(
        [e[:2] for e in g] == [e[:2] for e in w],
        f"{name}: events differ ({len(g)} vs {len(w)})",
    )
    worst = max((abs(a[2] - b[2]) for a, b in zip(g, w)), default=0.0)
    check(worst <= SCORE_TOL, f"{name}: score delta {worst!r} > {SCORE_TOL}")
    return worst


def golden_replay(sets, requests) -> list:
    """Golden answers for ``requests`` replayed in order on one fresh
    analyzer (frequency state carries across requests, as on the
    server)."""
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.golden import GoldenAnalyzer
    from log_parser_tpu.models.pod import PodFailureData

    golden = GoldenAnalyzer(sets, ScoringConfig.from_env())
    return [
        golden.analyze(
            PodFailureData(pod={"metadata": {"name": name}}, logs=logs)
        )
        for name, logs in requests
    ]


def serve_requests(label: str, engine, requests, want) -> tuple[list, dict]:
    """POST every request to ``engine`` behind HTTP, check each answer
    against golden, then read /trace/last."""
    served = Served(engine)
    try:
        bodies = []
        for (name, logs), expect in zip(requests, want):
            body, wall = served.post(name, logs)
            delta = compare(f"{label}/{name}", body, expect)
            say(
                f"{label}: {name} lines={logs.count(chr(10)) + 1} "
                f"events={len(body.get('events') or [])} "
                f"wall_s={wall!r} max_score_delta={delta!r}"
            )
            bodies.append(body)
        trace = served.trace()
    finally:
        served.close()
    check(trace["fallbackCount"] == 0,
          f"{label}: fallbackCount {trace['fallbackCount']}")
    check(trace["hostRoutedCount"] == 0,
          f"{label}: hostRoutedCount {trace['hostRoutedCount']}")
    return bodies, trace


def one_chip(args, compile_clock) -> None:
    from log_parser_tpu import native
    from log_parser_tpu.ops import bitglush_pallas
    from log_parser_tpu.ops.matchdfa_pallas import ADMITTED
    from log_parser_tpu.utils import corpus, xlacache

    sets = load_sets()
    requests = [
        ("config1-pod-failure", POD_FAILURE_LOG),
        (f"config2-{CONFIG2_LINES}", corpus.pod_log(CONFIG2_LINES)),
        (f"seeded-{SEEDED_LINES}", seeded_log(SEEDED_LINES, args.seed)),
    ]
    want = golden_replay(sets, requests)

    # phase 1: the default served path
    c0 = compile_clock.seconds
    engine = build_engine(sets)
    bodies, trace = serve_requests("served", engine, requests, want)
    check(native.available(), f"native library: {native.stats()}")
    say(f"native: {json.dumps(native.stats())}")
    say(f"served: compile_s={compile_clock.seconds - c0!r} "
        f"lineCache={json.dumps(trace.get('lineCache'))}")

    # phase 2: the Pallas kernels — bitglush where the tier plan puts
    # the builtin bank's columns, union-DFA in an off-path layout
    traced = {"bitglush": 0}
    real = bitglush_pallas.bitglush_hits_pallas

    def counting(*a, **k):
        traced["bitglush"] += 1
        return real(*a, **k)

    bitglush_pallas.bitglush_hits_pallas = counting
    try:
        c0 = compile_clock.seconds
        eng = build_engine(sets, pallas=True)
        check(eng.matchers.bitglush_use_pallas,
              "bitglush kernel not admitted: no bit tier")
        got, _ = serve_requests("kernel-bitglush", eng, requests, want)
        for (name, _), a, b in zip(requests, got, bodies):
            compare(f"kernel-bitglush/{name} vs served", a, b)
        check(traced["bitglush"] > 0, "bitglush kernel never traced")
        say(f"kernel-bitglush: traced={traced['bitglush']} "
            f"words={eng.matchers.bitglush.n_words} "
            f"compile_s={compile_clock.seconds - c0!r}")

        c0 = compile_clock.seconds
        eng = build_engine(sets, pallas=True, bit_words=0)
        got, trace = serve_requests("kernel-union-dfa", eng, requests,
                                    want)
        for (name, _), a, b in zip(requests, got, bodies):
            compare(f"kernel-union-dfa/{name} vs served", a, b)
        k = trace["kernel"]
        check(k["reason"] in ADMITTED, f"union-DFA kernel: {k['reason']}")
        check(k["kernelBatches"] > 0, f"union-DFA kernel never ran: {k}")
        say(f"kernel-union-dfa: layout=off-path reason={k['reason']} "
            f"kernelBatches={k['kernelBatches']} "
            f"kernelRows={k['kernelRows']} xlaBatches={k['xlaBatches']} "
            f"geometry={json.dumps(k['geometry'])} "
            f"compile_s={compile_clock.seconds - c0!r}")
    finally:
        bitglush_pallas.bitglush_hits_pallas = real
    say(f"compileCache: {json.dumps(xlacache.stats())}")


def four_chips(args, devices, compile_clock) -> None:
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.parallel import PatternShardedEngine, make_mesh
    from log_parser_tpu.utils import xlacache

    check(len(devices) >= 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    sets = load_sets()
    name = f"seeded-{SEEDED_LINES}"
    logs = seeded_log(SEEDED_LINES, args.seed)
    want = golden_replay(sets, [(name, logs)])[0]
    data = PodFailureData(pod={"metadata": {"name": name}}, logs=logs)

    # what the mesh engines are compared with: one chip
    single = build_engine(sets)
    t0 = time.perf_counter()
    ref = single.analyze(data)
    say(f"one-chip: events={len(ref.events)} "
        f"wall_s={time.perf_counter() - t0!r} "
        f"max_score_delta={compare('one-chip', ref, want)!r}")

    # line axis: the mesh program, served as serve --sharded does
    c0 = compile_clock.seconds
    sharded = build_engine(sets, sharded_mesh=make_mesh(4))
    outs = []
    real_jit = sharded.step._jit

    def spy(*a):
        out = real_jit(*a)
        outs.append(out)
        return out

    sharded.step._jit = spy
    (body,), _ = serve_requests("sharded-mesh4", sharded, [(name, logs)], [want])
    compare("sharded-mesh4 vs one-chip", body, ref)
    spanned = {d for out in outs for a in out for d in a.sharding.device_set}
    check(len(spanned) == 4, f"sharded outputs on {len(spanned)} device(s)")
    say(f"sharded-mesh4: output devices={len(spanned)} "
        f"compile_s={compile_clock.seconds - c0!r}")

    # pattern axis: one block per device
    c0 = compile_clock.seconds
    pse = PatternShardedEngine(
        sets, ScoringConfig.from_env(), devices=devices[:4], n_blocks=4
    )
    pse.fallback_to_golden = False
    placed = set()
    for fused, _, _ in pse._block_engines:
        real_dispatch = fused.dispatch

        def spy_dispatch(*a, _real=real_dispatch, **k):
            out = _real(*a, **k)
            placed.update(out.sharding.device_set)
            return out

        fused.dispatch = spy_dispatch
    t0 = time.perf_counter()
    got = pse.analyze(data)
    wall = time.perf_counter() - t0
    compare("pattern-sharded-4 vs golden", got, want)
    compare("pattern-sharded-4 vs one-chip", got, ref)
    check(len(placed) == 4, f"pattern blocks ran on {len(placed)} device(s)")
    check(pse.fallback_count == 0, "pattern-sharded engine fell back")
    say(f"pattern-sharded-4: events={len(got.events)} wall_s={wall!r} "
        f"block devices={len(placed)} "
        f"compile_s={compile_clock.seconds - c0!r}")
    say(f"compileCache: {json.dumps(xlacache.stats())}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # the library caches live in the checkout, beside the XLA cache
    os.environ.setdefault(
        "LOG_PARSER_TPU_CACHE", os.path.join(REPO, ".cache", "lib")
    )
    devices = device_or_exit()
    import jax

    compile_clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args, devices, compile_clock)
    else:
        one_chip(args, compile_clock)
    say(f"total: wall_s={time.perf_counter() - t0!r} "
        f"compile_s={compile_clock.seconds!r} compiles={compile_clock.count}")
    d = jax.devices()[0]
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": d.platform,
            "kind": d.device_kind,
            "count": len(jax.devices()),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
