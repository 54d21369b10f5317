"""Run one cell of ``BENCHMARK.json`` once and print one JSON result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip: it builds the engine as ``python -m
log_parser_tpu.serve --pattern-dir <library>`` does, serves it on
localhost, and starts the load generator (``loadgen.py``), a child that
never imports JAX. Set-up — chip start-up, the bank build, compiles or
their replay from the cache, the request pool and the warm-up requests —
ends when the first timed request is sent. The window then runs for
``--seconds``; with ``--trace 1`` the JAX profiler traces it. Once the
window's last answer is in and the server is gone, the plain reference
(``reference.py``) checks every answer the engine served (``check.py``).

Earlier lines report the device, the compiles in set-up and in the
window (there should be none), and how late the generator ran. The last
line on standard output is the result; the last lines on standard error
are the numbers compared, each beside its limit. Without an accelerator
of the kind the table of peaks knows, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

import os
import sys
import time

# Set-up is timed from this process's start, across the one re-exec
# below (the monotonic clock is the machine's, not the process's).
_T0_ENV = "BENCHMARK_RUN_STARTED"
T0 = float(os.environ.pop(_T0_ENV, None) or time.monotonic())

if __name__ == "__main__" and os.environ.get("PYTHONHASHSEED") != "0":
    # The program builds its bank in an order that follows Python's
    # string hashing, so under a random hash seed its compiled programs
    # differ from process to process, and whether the compile cache
    # hits is a matter of chance (PERF.md, Open questions). Pin it, so
    # that every run after the first finds its programs in the cache and
    # set-up does not swing with the draw.
    os.environ[_T0_ENV] = repr(T0)
    os.environ["PYTHONHASHSEED"] = "0"
    os.execv(sys.executable, [sys.executable] + sys.argv)

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.cell import load_cell, metric_reader  # noqa: E402

# the platform every measurement needs; a CPU rehearsal steers it
REQUIRED_PLATFORM = "tpu"
PEAKS_FILE = os.path.join(BENCH_DIR, "peaks.json")
# the request-trace ring keeps every request of a run: its order is the
# finalize order the reference replays
TRACE_RING = 1 << 20
# the host annotation that spans the measured window in a traced run
WINDOW_EVENT = "benchmark.window"


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Backend compiles (cache retrievals included) and their seconds,
    from JAX's own monitoring events."""

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


def device_or_exit(chips: int):
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != REQUIRED_PLATFORM or len(devices) < chips:
        print(f"run: JAX found {len(devices)} {d.platform!r} device(s); the "
              f"cell needs {chips} {REQUIRED_PLATFORM!r}", file=sys.stderr)
        sys.exit(2)
    with open(PEAKS_FILE, encoding="utf-8") as f:
        peaks = json.load(f)["devices"]
    if d.device_kind not in peaks:
        print(f"run: no peaks for device kind {d.device_kind!r}",
              file=sys.stderr)
        sys.exit(2)
    say(f"device: {d.platform} {d.device_kind} x{len(devices)}")
    return devices[:chips]


class Child:
    """The load generator, one JSON line per message each way. It runs
    in a process group of its own with the workers that make its
    requests, and ``stop`` ends the group."""

    def __init__(self):
        self.proc = subprocess.Popen(
            [sys.executable, "-u", os.path.join(BENCH_DIR, "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            start_new_session=True,
        )

    def tell(self, msg: dict) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def hear(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"load generator ended ({self.proc.wait()})")
        return json.loads(line)

    def stop(self) -> None:
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait(timeout=60)


def _quantile_ms(xs: list[float], p: float) -> float | None:
    from benchmark.measure import nearest_rank

    return nearest_rank(xs, p) * 1e3 if xs else None


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except KeyError as exc:
        print(f"run: {exc}", file=sys.stderr)
        return 2

    # the program's library and DFA caches live in the checkout, beside
    # its compile cache (<checkout>/.cache/xla)
    os.environ["LOG_PARSER_TPU_CACHE"] = os.path.join(ROOT, ".cache", "lib")
    os.environ["LOG_PARSER_TPU_TRACE_RING"] = str(TRACE_RING)
    # the load generator makes the requests while the chip starts up
    child = Child()
    trace_dir = None
    try:
        child.tell({"cell": {"config": cell.config, "traffic": cell.traffic},
                    "seed": args.seed, "seconds": args.seconds})
        devices = device_or_exit(cell.chips)
        t_device = time.monotonic() - T0

        import jax

        from benchmark import check, measure, trace_reduce
        from benchmark.libraries import library_dir
        from benchmark.server import Served

        lib_dir = library_dir(cell.config)
        clock = CompileClock()
        served = Served(cell.config, lib_dir)
        engine = served.engine
        say(f"library: {engine.bank.n_patterns} patterns, "
            f"{engine.bank.n_columns} columns, "
            f"{len(engine.skipped_patterns)} skipped")
        t_built = time.monotonic() - T0
        child.hear()  # requests made
        child.tell({"port": served.port})
        warm = child.hear()
        # the engine prices each new shape once, lowering it on a
        # background thread; let those finish before the window opens
        for th in threading.enumerate():
            if th.name == "dispatch-cost":
                th.join(timeout=120)
        setup_compiles, setup_compile_s = clock.count, clock.seconds
        gc.collect()  # the window starts without set-up's garbage
        before = served.scrape()
        if args.trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        setup_s = time.monotonic() - T0
        with jax.profiler.TraceAnnotation(WINDOW_EVENT):
            child.tell({"run": True})
            child.hear()  # closed
        if args.trace:
            jax.profiler.stop_trace()
        window_compiles = clock.count - setup_compiles
        final = child.hear()
        window_compiles_all = clock.count - setup_compiles
        pool_left = final["pool_left"]
        after = served.scrape()
        order = served.finalize_order()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        served.close()
        del served, engine
        gc.collect()
    finally:
        child.stop()

    records = final["records"]
    window = [r for r in records if not r["warmup"]]
    lateness = [r["sent"] - r["due"] for r in window]
    thirds = []
    for lo, hi in ((0, 1 / 3), (2 / 3, 1)):
        lat = [(r["done"] - r["due"]) * 1e3 for r in window if r["done"] is not None
               and lo * args.seconds <= r["due"] < hi * args.seconds]
        thirds.append(sum(lat) / len(lat) if lat else None)
    say(f"setup: setup_s={setup_s!r} device_s={t_device!r} "
        f"engine_built_s={t_built!r} compiles={setup_compiles} "
        f"compile_s={setup_compile_s!r} warmup_requests={warm['warm']} "
        f"warmup_failed={warm['failed']}")
    say(f"window: compiles_in_window={window_compiles} "
        f"compiles_until_last_answer={window_compiles_all} "
        f"requests={len(window)} lines={sum(r['lines'] for r in window)} "
        f"pool_left={pool_left} "
        f"last_answer_s={max((r['done'] or 0.0) for r in window) if window else None!r} "
        f"lateness_max_ms={_quantile_ms(lateness, 100)!r} "
        f"lateness_p95_ms={_quantile_ms(lateness, 95)!r} "
        f"latency_mean_ms_first_third={thirds[0]!r} "
        f"latency_mean_ms_last_third={thirds[1]!r}")

    trace = None
    if trace_dir is not None:
        try:
            trace, planes = trace_reduce.reduce_dir(trace_dir, WINDOW_EVENT)
            say(f"trace planes: {planes}")
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if trace is None:
            say("trace: no device plane, so no device metric")
        else:
            say(f"trace: busy_s={trace['busy_s']!r} "
                f"window_s={trace['window_s']!r}")

    t = time.monotonic()
    finalized = [rid for _seq, rid, outcome in order if outcome == "ok"]
    expected = check.replay(finalized, cell, args.seed, args.seconds, lib_dir)
    fallbacks = sum(
        v for (name, _), v in after.items()
        if name in ("logparser_fallback_total", "logparser_host_routed_total")
    )
    attempted = final["attempted"] if final["attempted"] is not None else len(window)
    checks = check.compare(records, expected, attempted, fallbacks,
                           cell.config["limits"])
    say(f"reference: requests={len(finalized)} "
        f"seconds={time.monotonic() - t!r}")

    run = measure.Run(args.seconds, setup_s, window, before, after, trace)
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {
        "correct": check.is_correct(checks),
        "attempted": attempted,
        "failed": attempted - len(run.answered),
        "metrics": metrics,
        "device": device,
    }
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    print(json.dumps(result), flush=True)
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
