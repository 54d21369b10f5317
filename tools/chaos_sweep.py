"""Chaos sweep: the fault-injection DSL against a LIVE server.

The chaos tests (tests/test_admission.py, tests/test_faults.py) exercise
the ladder in-process; this tool runs the same scenarios the way an
operator meets them — a real ``python -m log_parser_tpu.serve`` child
process, concurrent HTTP clients, signals — and prints a pass/fail table.
Every scenario pins ``LOG_PARSER_TPU_FAULT_SEED``, so a failing row
reproduces bit-identically when re-run.

Scenarios:

- ``baseline``        no faults — every request 200.
- ``device-raise``    probabilistic device faults — every request still
                      200 (golden fallback absorbs them), fallbackCount
                      moved, NOTHING shed.
- ``device-wedge``    a permanent device hang under ``--device-timeout``
                      — breaker opens, service stays 200 from the host
                      path, health shows DEGRADED.
- ``queue-shed``      slow ingest + max-inflight=1/max-queue=1 + a burst
                      — some 200s, some 429s carrying Retry-After.
- ``drain``           SIGTERM with a slow request in flight — in-flight
                      answered 200, /health/ready 503 during drain,
                      child exits 0.

Batcher group (``--group batcher``; micro-batching on — docs/OPS.md
"Micro-batching"):

- ``batch-coalesce``     a burst under a generous --batch-wait-ms —
                         every request 200, /trace/last shows real
                         coalescing (maxBatchSeen ≥ 2).
- ``batch-demux-drop``   a seeded ``batcher_demux`` fault drops ONE
                         demux slot — exactly that request 500s, its
                         batchmates answer 200 untouched.
- ``batch-device-fault`` an injected device fault fails a WHOLE batch —
                         every member still answers 200 from the golden
                         per-request fallback.

State group (``--group state``; durable frequency state + hot reload —
docs/OPS.md "State durability & recovery"):

- ``state-kill9-replay``     N requests, SIGKILL mid-stream, restart on
                             the same ``--state-dir``, remainder — final
                             frequency stats and scores identical to an
                             uninterrupted run.
- ``state-torn-tail``        a ``journal_torn`` fault leaves half a
                             frame as the WAL's final bytes — the
                             restart quarantines it to ``.torn``,
                             replays every whole record, and serves.
- ``state-canary-rollback``  an injected ``reload_canary`` fault turns
                             ``POST /patterns/reload`` into a structured
                             409 — the old banks keep serving, scores
                             unchanged; the next reload (budget spent)
                             succeeds.
- ``state-reload-under-load``  a concurrent burst of batched requests
                             races a hot reload — zero failed requests,
                             the reload completes, epoch bumps.

Poison group (``--group poison``; quarantine + bisection + shadow
verification — docs/OPS.md "Poison-request triage" / "Shadow
divergence"):

- ``poison-batch-isolate``     ONE poison request inside a 16-request
                               batched stream: bisection isolates it, the
                               other 15 are served ON-DEVICE (zero
                               fallbacks for them), the poison serves
                               from golden, its fingerprint quarantines,
                               and a repeat never reaches the device step
                               (the keyed fault's fire counter is pinned).
- ``poison-ttl-readmit``       a quarantined fingerprint is served
                               golden without touching the device until
                               ``--quarantine-ttl-s`` expires, then
                               re-admitted to the device step with a
                               clean slate.
- ``shadow-divergence-breaker``  an injected ``shadow`` divergence flips
                               /q/health to DEGRADED and opens the
                               pattern's breaker; after the cool-down the
                               half-open probe (forced shadow sample)
                               closes it and health recovers.

Linecache group (``--group linecache``; routing-tier template cache —
docs/OPS.md "Line cache (routing tier)"):

- ``linecache-hit-under-reload-swap``  a burst of cache-hit requests
                               races a hot pattern reload — zero failed
                               requests, the swap flushes the cache
                               exactly once (epochFlushes bumps), and
                               the new epoch repopulates it.
- ``linecache-eviction-under-load``  a cache budgeted far below the
                               working set keeps serving exact results
                               while evicting LRU lines and never
                               exceeds its resident-byte ceiling.
- ``linecache-breaker-partial-invalidation``  a shadow-divergence
                               breaker trip while the stream is served
                               from cache: the tripped pattern's
                               columns re-evaluate from the exact host
                               regex over CACHED rows (per-pattern
                               invalidation by construction) and the
                               other patterns keep hitting the cache.
- ``linecache-shadow-parity``  rate-1.0 online shadow verification over
                               a cache-served stream — every response,
                               including all-hit requests that never
                               touch the device, re-runs on the golden
                               host path; zero divergences is the
                               in-service cache-on ≡ cache-off proof.

Distributed group (``--group distributed``; needs a jax build whose CPU
backend supports multi-process collectives — reported SKIP otherwise):

- ``follower-degrade``  coordinator + follower sharing a jax.distributed
                        runtime; a seeded follower hang exhausts the
                        bounded-broadcast budget, requests keep answering
                        200 with the ``degraded: distributed-fallback``
                        marker, the heartbeat re-admits the mesh
                        (/trace/last ``distributed.mode`` back to
                        ``distributed``), and SIGTERM still shuts both
                        processes down cleanly.

kernel group (--group kernel): the Pallas union-DFA kernel tier behind
                        --pallas-dfa. One scenario pins the /trace/last
                        ``kernel`` verdict block (admission reason +
                        dispatch counters); the other arms a
                        ``kernel_raise`` fault and proves the kernel
                        tier does not swallow it: the golden fallback
                        serves that request and counts it
                        (fallbackCount 1), and later requests ride
                        the kernel again.

Streaming group (``--group streaming``; follow-mode sessions —
docs/OPS.md "Streaming follow-mode"):

- ``stream-device-fault-golden``  an injected device fault mid-session
                        flips the session to a golden continuation: it
                        keeps emitting, closes with a ``final`` frame,
                        and ``stream.goldenContinuations`` moves — the
                        client never sees the fault.
- ``stream-poison-kill``  a keyed poison chunk kills exactly its own
                        SESSION (structured ``error`` frame, reason
                        ``poison``, fingerprint struck) — the server and
                        a parallel fresh session keep serving.
- ``stream-reload-rebase``  a hot pattern reload lands while a session
                        is open between chunks; the next chunk re-bases
                        the session onto the new banks
                        (``sessionsRebased`` bumps) and it still closes
                        with a ``final`` frame.
- ``stream-ttl-reap``   idle sessions under ``--stream-ttl-s 1`` are
                        reaped while a concurrent parse burst runs —
                        their admission slots release
                        (``openSessions`` 0, gate ``inflight`` 0) and
                        the server stays healthy.

Tenant group (``--group tenant``; multi-tenant serving — docs/OPS.md
"Multi-tenant serving"):

- ``tenant-quota-shed``     one tenant's lines/s bucket empties under a
                        run of requests — that tenant gets structured
                        429s (``reason: tenant rate``, Retry-After ≥ 1)
                        while the default tenant keeps answering 200;
                        /trace/last pins ``admission.shedTenant`` and
                        the tenant's ``quota.shedRate``.
- ``tenant-evict-rebuild``  a bank budget sized for ~1.5 tenants forces
                        LRU eviction when a second tenant arrives and a
                        rebuild when the first returns — every request
                        (including a concurrent default-tenant burst)
                        still answers 200 and the ``tenants`` trace
                        block shows ``evicted``/``rebuilds`` moving.
- ``tenant-reload-isolated``  a hot pattern reload scoped to tenant A
                        (``X-Tenant`` on ``POST /patterns/reload``)
                        races a burst of tenant-B traffic — zero failed
                        B requests, A's ``reloadEpoch`` bumps, B's and
                        the default tenant's stay put.

Miner group (``--group miner``; template miner — docs/OPS.md "Template
miner"):

- ``miner-tap-overflow``    a wedged miner worker (``miner_hang:inf``)
                        under a 4-slot tap — the bounded queue fills,
                        ``miner.dropped`` climbs on /trace/last, and the
                        hot path never notices (every request 200).
- ``miner-reject-identity``  a candidate rejected at the vet gates
                        (byte-identical to a curated regex) leaves the
                        serving bank OBJECT-identical and the reload
                        epoch untouched.
- ``miner-reload-race``     mined admission racing a concurrent curated
                        reload under the quiesce gate — a clean
                        retryable ``mined-swap``, curated reload lands
                        first, the candidate re-admits on a later pump
                        against the post-reload library.

Spans group (``--group spans``; causal span tracing — docs/OPS.md "Span
tracing & utilization accounting"):

- ``spans-fault-site``   a device fault under micro-batching — the
                        faulted dispatch records its span carrying the
                        failure attr, the same flush trace still closes
                        with its demux span, and flush/request traces
                        keep linking each other both ways.
- ``spans-sample-drop``  ``--trace-sample 0`` with the slow bar lifted
                        out of reach — request traces are dropped,
                        force-kept flush traces still commit, and the
                        staging dict drains to zero (no orphans).

Migrate group (``--group migrate``; crash-safe tenant live migration +
health-driven drain — docs/OPS.md "Tenant migration & drain"):

- ``migrate-live-cutover``     acme moves between two processes over
                        HTTP; the source 307-forwards with Location +
                        Retry-After, the target serves the migrated
                        state.
- ``migrate-crash-mid-export`` the ``migrate_export`` fault under the
                        quiesce gate: structured 409 abort, the source
                        keeps the tenant, no forward.
- ``migrate-crash-pre-cutover`` the ``migrate_cutover`` fault after the
                        target staged: the source aborts and keeps
                        serving; the target's staged copy never
                        activates (single-owner invariant).
- ``migrate-drain-under-burst`` /admin/drain races a burst: every
                        tenant closes under --drain-deadline-s,
                        /q/health flips to a DRAINING 503, SIGTERM
                        exits clean.
- ``migrate-stream-handoff``   a live follow-mode session on the moving
                        tenant is closed with an explicit error frame
                        naming the new owner — cutover never hangs on
                        a pinned stream.

Replica group (``--group replica``; warm-standby replication + fenced
failover — docs/OPS.md "Warm-standby replication & failover"):

- ``replica-failover-kill9``   a live primary/standby pair shipping WAL
                        (``logparser_replication_lag_*`` visible on
                        /metrics) loses its primary to SIGKILL; the
                        armed supervisor promotes the standby, which
                        then serves the tenant's replicated history.
- ``replica-stale-primary-demotes`` the standby is promoted while the
                        primary is still alive (the operator error the
                        fence exists for): the stale primary's next
                        shipped batch is refused with the higher
                        epoch, it demotes itself, and client traffic
                        307-forwards to the new owner.
- ``replica-lagging-promotion`` the primary is killed with an unshipped
                        WAL tail; a manual /admin/promote serves the
                        acked prefix — the documented state-loss bound
                        — and the promotion is journaled.

Fleet group (``--group fleet``; router front-door + signal-driven
placement — docs/OPS.md "Fleet routing & placement"): a real
``--role router`` process over real backend serving processes.

- ``fleet-backend-kill-reroute`` a backend dies by SIGKILL mid-fleet:
                        the ring evicts it after ``--fleet-down-after``
                        failures, every subsequent request is served by
                        the survivors (zero client errors), and the
                        router's health + ``logparser_fleet_*`` metrics
                        reflect the loss.
- ``fleet-hot-tenant-automove`` one tenant burns its quota (429 sheds):
                        the placer scrapes the shed rate off the
                        backend's /metrics and live-migrates the tenant
                        to the least-loaded backend; clients see only
                        200s and structured 429s, never a 5xx, and the
                        tenant serves from its new owner afterwards.
- ``fleet-budget-rebalance`` fleet-arbitrated budgets replace the
                        per-process flags: the router pushes
                        traffic-derived line-cache + tenant-residency
                        shares via POST /admin/budget and both sides
                        agree — the backend's /trace/last shows the
                        applied share, the router's /fleet/status the
                        assignment.

Pressure group (``--group pressure``; resource-exhaustion robustness —
docs/OPS.md "Resource exhaustion"):

- ``pressure-soft-compaction`` a forced ``watermark:soft`` raise: the
                        ladder reclaims (a seeded terminal migration
                        journal compacts to its decision records),
                        /q/health carries a DEGRADED pressure check,
                        and responses stay 200 WITHOUT a durability
                        stamp — soft never downgrades durability.
- ``pressure-hard-degrade-rearm`` a @times-bounded ``watermark:hard``
                        raise: 200s stamped ``durability: degraded``
                        with the WAL diverted to the in-memory ring,
                        then automatic hysteretic recovery — the stamp
                        disappears and fsync'd journaling re-arms from
                        a clean snapshot barrier.
- ``pressure-retry-storm-shed`` a dead backend under an armed
                        ``retry_storm`` fault: router re-route retries
                        shed structured 503s (``retry budget
                        exhausted``) and the service recovers once the
                        corpse is evicted; the identical kill with
                        ``--retry-budget 0`` retries unbounded to a
                        200 — the storm the budget prevents.

Usage: python tools/chaos_sweep.py [--only NAME]
                                   [--group base|batcher|state|poison|linecache|kernel|streaming|distributed|tenant|miner|obs|spans|migrate|replica|fleet|pressure|all]
                                   [--keep-logs]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# in-process drills import log_parser_tpu directly (script mode puts
# tools/ on sys.path, not the repo root)
sys.path.insert(0, REPO)
PATTERN_DIR = os.path.join(REPO, "log_parser_tpu", "patterns", "builtin")
LOGS = "INFO boot\njava.lang.OutOfMemoryError: heap\nINFO after"
PAYLOAD = json.dumps(
    {"pod": {"metadata": {"name": "chaos"}}, "logs": LOGS}
).encode()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def post(url: str, headers: dict | None = None, timeout: float = 30.0):
    req = urllib.request.Request(
        url + "/parse",
        data=PAYLOAD,
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def get(url: str, path: str):
    try:
        with urllib.request.urlopen(url + path, timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class Server:
    """One serve child; scenario args via CLI flags, chaos via env."""

    def __init__(self, name: str, args: list[str], env: dict[str, str],
                 port: int | None = None):
        # replica pairs need each other's URL at boot, so their ports are
        # allocated up front and passed in
        self.port = port or free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.log = tempfile.NamedTemporaryFile(
            "wb", prefix=f"chaos_{name}_", suffix=".log", delete=False
        )
        child_env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "PYTHONUNBUFFERED": "1",
            **env,
        }
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", "log_parser_tpu.serve",
                "--pattern-dir", PATTERN_DIR,
                "--host", "127.0.0.1", "--port", str(self.port),
                *args,
            ],
            cwd=REPO,
            env=child_env,
            stdout=self.log,
            stderr=subprocess.STDOUT,
        )

    def wait_ready(self, timeout: float = 90.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited rc={self.proc.returncode} before ready "
                    f"(log: {self.log.name})"
                )
            try:
                status, _ = get(self.url, "/health/ready")
                if status == 200:
                    return
            except OSError:
                pass
            time.sleep(0.25)
        raise RuntimeError(f"server never became ready (log: {self.log.name})")

    def stop(self, expect_zero: bool = False) -> int:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(10)
        rc = self.proc.returncode
        if expect_zero and rc != 0:
            raise AssertionError(f"expected clean exit, got rc={rc}")
        return rc


class Burst:
    """N concurrent posts; collect (status, headers) pairs."""

    def __init__(self, url: str, n: int, headers: dict | None = None):
        self.results: list[tuple[int, dict]] = []
        self._lock = threading.Lock()

        def one():
            status, _, hdrs = post(url, headers)
            with self._lock:
                self.results.append((status, hdrs))

        self.threads = [threading.Thread(target=one) for _ in range(n)]
        for t in self.threads:
            t.start()

    def join(self, timeout: float = 60.0):
        for t in self.threads:
            t.join(timeout)
        assert all(not t.is_alive() for t in self.threads), "burst stuck"
        return self.results


# ------------------------------------------------------------- scenarios


def scenario_baseline(srv: Server):
    for _ in range(4):
        status, body, _ = post(srv.url)
        assert status == 200, f"expected 200, got {status}"
        assert body["summary"]["significantEvents"] >= 1
    _, trace = get(srv.url, "/trace/last")
    assert trace["fallbackCount"] == 0, trace["fallbackCount"]


def scenario_device_raise(srv: Server):
    statuses = [post(srv.url)[0] for _ in range(12)]
    assert statuses == [200] * 12, statuses
    _, trace = get(srv.url, "/trace/last")
    fired = trace["faults"]["fired"]["device_raise"]
    assert 0 < fired < 12, f"seeded p=0.5 fired {fired}/12"
    assert trace["fallbackCount"] == fired, trace
    assert trace["admission"]["shedQueueFull"] == 0


def scenario_device_wedge(srv: Server):
    # warm up off the wedge (after=1), then hit it: still 200, via golden
    assert post(srv.url)[0] == 200
    statuses = [post(srv.url)[0] for _ in range(3)]
    assert statuses == [200] * 3, statuses
    status, health = get(srv.url, "/health")
    assert status == 200 and health.get("checks"), health
    assert health["checks"][0]["status"] == "DEGRADED", health
    _, trace = get(srv.url, "/trace/last")
    assert trace["deviceCircuitOpen"] is True
    assert trace["fallbackCount"] >= 1


def scenario_queue_shed(srv: Server):
    post(srv.url)  # warm: XLA compile outside the contended burst
    results = Burst(srv.url, 6).join()
    codes = sorted(s for s, _ in results)
    assert codes.count(200) >= 2, codes
    assert codes.count(429) >= 1, codes
    for status, hdrs in results:
        if status == 429:
            assert int(hdrs["Retry-After"]) >= 1, hdrs
    _, trace = get(srv.url, "/trace/last")
    assert trace["admission"]["shedQueueFull"] >= 1, trace["admission"]


def scenario_drain(srv: Server):
    post(srv.url)  # warm
    slow = Burst(srv.url, 1)  # ingest_slow holds this one in flight
    time.sleep(0.4)
    srv.proc.send_signal(signal.SIGTERM)
    deadline = time.monotonic() + 10
    saw_unready = False
    while time.monotonic() < deadline and not saw_unready:
        try:
            status, _ = get(srv.url, "/health/ready")
            saw_unready = status == 503
        except OSError:  # listener already gone: drain finished
            break
        time.sleep(0.05)
    results = slow.join()
    assert results[0][0] == 200, f"in-flight request got {results[0][0]}"
    srv.proc.wait(30)
    assert srv.proc.returncode == 0, f"rc={srv.proc.returncode}"
    assert saw_unready, "never observed /health/ready 503 during drain"


# ----------------------------------------------------- batcher scenarios


def scenario_batch_coalesce(srv: Server):
    post(srv.url)  # warm: compile the R=1 batch program off the clock
    results = Burst(srv.url, 6).join(timeout=120)
    codes = sorted(s for s, _ in results)
    assert codes == [200] * 6, codes
    _, trace = get(srv.url, "/trace/last")
    b = trace["batcher"]
    assert b["requestsBatched"] >= 7, b  # warm + burst all went through it
    assert b["maxBatchSeen"] >= 2, f"burst never coalesced: {b}"
    assert b["flushFull"] + b["flushWait"] >= 1, b
    assert trace["fallbackCount"] == 0, trace["fallbackCount"]


def scenario_batch_demux_drop(srv: Server):
    # two warm posts burn the fault's after=2 budget outside the burst
    assert post(srv.url)[0] == 200
    assert post(srv.url)[0] == 200
    results = Burst(srv.url, 4).join(timeout=120)
    codes = sorted(s for s, _ in results)
    # the dropped demux slot fails exactly ONE request; batchmates are
    # untouched — the containment contract of runtime/batcher.py
    assert codes == [200, 200, 200, 500], codes
    _, trace = get(srv.url, "/trace/last")
    assert trace["batcher"]["demuxErrors"] == 1, trace["batcher"]
    assert trace["faults"]["fired"]["batcher_demux_raise"] == 1, trace["faults"]
    assert trace["fallbackCount"] == 0, trace["fallbackCount"]


def scenario_batch_device_fault(srv: Server):
    post(srv.url)  # warm: one device call burns after=1
    results = Burst(srv.url, 4).join(timeout=120)
    codes = sorted(s for s, _ in results)
    # a transient device failure of the shared step never 500s anybody:
    # bisection retries the halves on-device (a coalesced batch), or —
    # if the faulted flush held a single request — that one serves from
    # the golden host path
    assert codes == [200] * 4, codes
    _, trace = get(srv.url, "/trace/last")
    b = trace["batcher"]
    assert b["bisects"] + trace["fallbackCount"] >= 1, trace
    assert trace["fallbackCount"] <= 1, trace["fallbackCount"]
    assert b["demuxErrors"] == 0, b


BATCHER_FLAGS = ["--batching", "on", "--batch-wait-ms", "200", "--batch-max", "8"]

BATCHER_SCENARIOS = [
    ("batch-coalesce", BATCHER_FLAGS, {}, scenario_batch_coalesce),
    (
        "batch-demux-drop",
        BATCHER_FLAGS,
        {
            "LOG_PARSER_TPU_FAULTS": "batcher_demux_raise@times=1@after=2",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_batch_demux_drop,
    ),
    (
        "batch-device-fault",
        BATCHER_FLAGS,
        {
            "LOG_PARSER_TPU_FAULTS": "device_raise@times=1@after=1",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_batch_device_fault,
    ),
]


# ------------------------------------------------------ poison scenarios


POISON_LOGS = "INFO boot\nPOISON-PILL marker line\njava.lang.OutOfMemoryError: heap"


def post_logs(url: str, logs: str, timeout: float = 240.0):
    body = json.dumps(
        {"pod": {"metadata": {"name": "chaos"}}, "logs": logs}
    ).encode()
    req = urllib.request.Request(
        url + "/parse", data=body, headers={"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _poll_trace(url: str, pred, timeout: float = 30.0) -> dict:
    """Poll /trace/last until ``pred(trace)`` — shadow verification is
    asynchronous, its counters land after the response."""
    deadline = time.monotonic() + timeout
    trace: dict = {}
    while time.monotonic() < deadline:
        _, trace = get(url, "/trace/last")
        if pred(trace):
            return trace
        time.sleep(0.2)
    raise AssertionError(f"trace never satisfied predicate: {trace}")


def scenario_poison_batch_isolate(srv: Server):
    """The acceptance scenario: ONE poison request inside a 16-request
    batched stream causes zero failures for the other 15 (served
    on-device after bisection), the poison fingerprint quarantines, and a
    repeat never reaches the device step again."""
    post(srv.url)  # warm: compile the R=1 batch program off the clock
    results: list[int] = []
    lock = threading.Lock()

    def one(logs: str) -> None:
        status, _, _ = post_logs(srv.url, logs)
        with lock:
            results.append(status)

    threads = [
        threading.Thread(target=one, args=(LOGS,)) for _ in range(15)
    ] + [threading.Thread(target=one, args=(POISON_LOGS,))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(240)
    assert all(not t.is_alive() for t in threads), "burst stuck"
    assert results == [200] * 16, sorted(results)
    _, trace = get(srv.url, "/trace/last")
    b, q = trace["batcher"], trace["quarantine"]
    # exactly the poison row fell back to golden; the healthy 15 were
    # served on-device (a fallback for any of them would show here)
    assert trace["fallbackCount"] == 1, trace["fallbackCount"]
    assert b["bisects"] >= 1, b
    assert b["bisectIsolated"] == 1, b
    assert b["demuxErrors"] == 0, b
    assert q["quarantined"] == 1 and q["active"] == 1, q
    fired_before = trace["faults"]["fired"]["quarantine_raise"]
    # the repeat is routed straight to golden: the keyed fault sits at
    # the device-step boundary, so its fire counter CANNOT move
    status, _, _ = post_logs(srv.url, POISON_LOGS)
    assert status == 200, status
    _, trace = get(srv.url, "/trace/last")
    assert trace["faults"]["fired"]["quarantine_raise"] == fired_before, (
        trace["faults"]
    )
    assert trace["quarantine"]["servedGolden"] >= 1, trace["quarantine"]


def scenario_poison_ttl_readmit(srv: Server):
    """Quarantine TTL expiry: the fingerprint serves golden until the TTL
    lapses, then re-admits to the device step with a clean slate."""
    post(srv.url)  # warm
    # strike 1 (--quarantine-strikes 1): fault fires, golden serves, the
    # fingerprint quarantines
    status, _, _ = post_logs(srv.url, POISON_LOGS)
    assert status == 200, status
    _, trace = get(srv.url, "/trace/last")
    assert trace["quarantine"]["quarantined"] == 1, trace["quarantine"]
    assert trace["faults"]["fired"]["quarantine_raise"] == 1, trace["faults"]
    # inside the TTL: served golden, the device step is never evaluated
    calls_before = trace["faults"]["calls"]["quarantine_raise"]
    status, _, _ = post_logs(srv.url, POISON_LOGS)
    assert status == 200, status
    _, trace = get(srv.url, "/trace/last")
    assert trace["faults"]["calls"]["quarantine_raise"] == calls_before, (
        trace["faults"]
    )
    assert trace["quarantine"]["servedGolden"] >= 1, trace["quarantine"]
    # past the TTL: re-admitted to the device step (the keyed fault is
    # evaluated again — its budget is spent, so the request succeeds
    # on-device)
    time.sleep(2.4)
    status, _, _ = post_logs(srv.url, POISON_LOGS)
    assert status == 200, status
    _, trace = get(srv.url, "/trace/last")
    assert trace["quarantine"]["readmitted"] == 1, trace["quarantine"]
    assert trace["quarantine"]["active"] == 0, trace["quarantine"]
    assert trace["faults"]["calls"]["quarantine_raise"] > calls_before, (
        trace["faults"]
    )


def scenario_shadow_divergence_breaker(srv: Server):
    """An injected shadow divergence (rate 1.0) must flip /q/health to
    DEGRADED and open the pattern's breaker; the half-open probe after
    the 1s cool-down closes it and health recovers."""
    assert post(srv.url)[0] == 200  # warm comparison (fault after=1: clean)
    _poll_trace(srv.url, lambda t: t.get("shadow", {}).get("compared", 0) >= 1)
    assert post(srv.url)[0] == 200  # this one's comparison diverges
    trace = _poll_trace(
        srv.url, lambda t: t.get("shadow", {}).get("divergences", 0) >= 1
    )
    sh = trace["shadow"]
    assert sh["divergences"] == 1, sh
    assert sh["breakers"]["open"], sh["breakers"]
    assert sh["breakers"]["trips"] == 1, sh["breakers"]
    _, health = get(srv.url, "/q/health")
    assert {"name": "shadow", "status": "DEGRADED"} in health.get("checks", []), (
        health
    )
    # requests keep answering 200 while the divergent pattern serves from
    # the exact host regex
    assert post(srv.url)[0] == 200
    # cool-down expiry → half-open → the forced shadow sample on the next
    # request closes the breaker (fault budget spent: comparison is clean)
    time.sleep(1.4)
    assert post(srv.url)[0] == 200
    trace = _poll_trace(
        srv.url,
        lambda t: t.get("shadow", {}).get("breakers", {}).get("closes", 0) >= 1,
    )
    br = trace["shadow"]["breakers"]
    assert not br["open"] and not br["halfOpen"], br
    _, health = get(srv.url, "/q/health")
    assert {"name": "shadow", "status": "DEGRADED"} not in health.get(
        "checks", []
    ), health


POISON_SCENARIOS = [
    (
        "poison-batch-isolate",
        [
            "--batching", "on", "--batch-wait-ms", "500", "--batch-max", "16",
            "--quarantine-strikes", "1", "--quarantine-ttl-s", "600",
        ],
        {
            "LOG_PARSER_TPU_FAULTS": "quarantine_raise@match=POISON-PILL",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_poison_batch_isolate,
    ),
    (
        "poison-ttl-readmit",
        ["--quarantine-strikes", "1", "--quarantine-ttl-s", "2"],
        {
            "LOG_PARSER_TPU_FAULTS": "quarantine_raise@match=POISON-PILL@times=1",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_poison_ttl_readmit,
    ),
    (
        "shadow-divergence-breaker",
        ["--shadow-rate", "1.0"],
        {
            "LOG_PARSER_TPU_FAULTS": "shadow_raise@times=1@after=1",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
            "LOG_PARSER_TPU_PATTERN_BREAKER_COOLDOWN_S": "1",
        },
        scenario_shadow_divergence_breaker,
    ),
]


# --------------------------------------------------- linecache scenarios


def scenario_linecache_reload_swap(srv: Server):
    """A burst of cache-hit requests racing a hot pattern reload: zero
    failed requests, the swap flushes the routing tier exactly once
    (epochFlushes bumps), and the new epoch repopulates the cache — a
    stale hit across the pattern swap is impossible."""
    for _ in range(2):  # warm: miss+populate, then all-hit
        assert post(srv.url)[0] == 200
    _, trace = get(srv.url, "/trace/last")
    lc = trace["lineCache"]
    assert lc["entries"] > 0 and lc["hits"] > 0, lc
    burst = Burst(srv.url, 8)
    time.sleep(0.05)  # let the burst enqueue before the swap quiesces
    status, body = post_raw(srv.url, "/patterns/reload", b"")
    results = burst.join(timeout=120)
    codes = sorted(s for s, _ in results)
    assert codes == [200] * 8, codes
    assert status == 200 and body["epoch"] == 1, (status, body)
    # the swapped banks serve the next request and repopulate the cache
    status, body, _ = post(srv.url)
    assert status == 200, status
    assert body["summary"]["significantEvents"] >= 1, body["summary"]
    _, trace = get(srv.url, "/trace/last")
    lc = trace["lineCache"]
    assert lc["epochFlushes"] == 1, lc
    assert lc["entries"] > 0, lc
    assert trace["reload"]["epoch"] == 1, trace["reload"]
    assert trace["fallbackCount"] == 0, trace["fallbackCount"]


def scenario_linecache_eviction(srv: Server):
    """A cache budgeted far below the working set must keep serving
    exact results while evicting LRU lines, and its resident bytes must
    never exceed the configured ceiling."""
    for r in range(6):
        logs = "\n".join(
            f"INFO unique filler {r}.{i} status=ok" for i in range(40)
        ) + "\njava.lang.OutOfMemoryError: heap"
        status, body, _ = post_logs(srv.url, logs)
        assert status == 200, status
        assert body["summary"]["significantEvents"] >= 1, body["summary"]
    _, trace = get(srv.url, "/trace/last")
    lc = trace["lineCache"]
    assert lc["evictions"] > 0, lc
    assert lc["residentBytes"] <= EVICTION_BUDGET_MB * 1024 * 1024, lc
    assert lc["entries"] > 0, lc
    assert trace["fallbackCount"] == 0, trace["fallbackCount"]


def scenario_linecache_breaker_partial(srv: Server):
    """A shadow-divergence breaker trip while the stream is served from
    cache: the tripped pattern's columns re-evaluate from the exact host
    regex over CACHED rows (per-pattern invalidation by construction —
    the host override cube is spliced over cached and fresh bits alike),
    requests stay 200 with the correct event, and the other patterns
    keep hitting the cache."""
    assert post(srv.url)[0] == 200  # miss+populate; comparison clean (after=1)
    _poll_trace(srv.url, lambda t: t.get("shadow", {}).get("compared", 0) >= 1)
    assert post(srv.url)[0] == 200  # all-hit; this comparison diverges
    trace = _poll_trace(
        srv.url, lambda t: t.get("shadow", {}).get("divergences", 0) >= 1
    )
    assert trace["shadow"]["breakers"]["open"], trace["shadow"]["breakers"]
    hits_before = trace["lineCache"]["hits"]
    # breaker open: the request still serves from cache (hits grow) and
    # the divergent pattern's verdict comes from the exact host regex
    status, body, _ = post(srv.url)
    assert status == 200, status
    assert body["summary"]["significantEvents"] >= 1, body["summary"]
    _, trace = get(srv.url, "/trace/last")
    assert trace["lineCache"]["hits"] > hits_before, trace["lineCache"]
    assert trace["fallbackCount"] == 0, trace["fallbackCount"]


def scenario_linecache_shadow_parity(srv: Server):
    """Rate-1.0 online shadow verification over a cache-served stream —
    every response, including the all-hit requests that never touch the
    device, re-runs on the golden host path and compares events and
    scores. Zero divergences IS the in-service cache-on ≡ cache-off
    proof."""
    for _ in range(6):
        assert post(srv.url)[0] == 200
    trace = _poll_trace(
        srv.url, lambda t: t.get("shadow", {}).get("compared", 0) >= 6
    )
    assert trace["shadow"]["divergences"] == 0, trace["shadow"]
    lc = trace["lineCache"]
    # requests 2..6 are served wholly from cache (3 lines each)
    assert lc["hits"] >= 15, lc
    assert trace["fallbackCount"] == 0, trace["fallbackCount"]


EVICTION_BUDGET_MB = 0.002  # ≈ 16 entries at the builtin bank's row width

LINECACHE_SCENARIOS = [
    (
        "linecache-hit-under-reload-swap",
        [
            "--line-cache-mb", "64",
            "--batching", "on", "--batch-wait-ms", "20", "--batch-max", "8",
        ],
        {},
        scenario_linecache_reload_swap,
    ),
    (
        "linecache-eviction-under-load",
        ["--line-cache-mb", str(EVICTION_BUDGET_MB)],
        {},
        scenario_linecache_eviction,
    ),
    (
        "linecache-breaker-partial-invalidation",
        ["--line-cache-mb", "64", "--shadow-rate", "1.0"],
        {
            "LOG_PARSER_TPU_FAULTS": "shadow_raise@times=1@after=1",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
            "LOG_PARSER_TPU_PATTERN_BREAKER_COOLDOWN_S": "600",
        },
        scenario_linecache_breaker_partial,
    ),
    (
        "linecache-shadow-parity",
        ["--line-cache-mb", "64", "--shadow-rate", "1.0"],
        {},
        scenario_linecache_shadow_parity,
    ),
]


# ------------------------------------------------------ kernel scenarios


def scenario_kernel_tier_engaged(srv: Server):
    """--pallas-dfa on: the trace surfaces the tier verdict. On hosts
    where the union tier packs groups the kernel dispatches (or reports
    a concrete admission reason); everywhere the responses stay
    correct."""
    for _ in range(3):
        status, body, _ = post(srv.url)
        assert status == 200, status
        assert body["summary"]["significantEvents"] >= 1, body["summary"]
    _, trace = get(srv.url, "/trace/last")
    k = trace["kernel"]
    assert k["reason"] in (
        "byte_classed", "split", "no_union_groups", "table_too_large",
        "no_tile",
    ), k
    if k["enabled"] and k["reason"] in ("byte_classed", "split"):
        assert k["kernelBatches"] >= 1, k
    assert trace["fallbackCount"] == 0, trace["fallbackCount"]


def scenario_kernel_fault_raises(srv: Server):
    """An armed kernel fault is never swallowed by the kernel tier: it
    raises as a device error, the golden fallback serves that request
    (clients still see 200) and counts it in fallbackCount, and the
    next request's trace runs the kernel again."""
    for _ in range(3):
        status, body, _ = post(srv.url)
        assert status == 200, status
        assert body["summary"]["significantEvents"] >= 1, body["summary"]
    _, trace = get(srv.url, "/trace/last")
    k = trace["kernel"]
    if k["enabled"]:
        fired = trace.get("faults", {}).get("fired", {})
        assert fired.get("kernel_raise", 0) == 1, fired
        assert trace["fallbackCount"] == 1, trace["fallbackCount"]
        assert k["reason"] in ("byte_classed", "split"), k
        assert k["kernelBatches"] >= 1, k
    else:  # no union groups on this host: the fire site is never reached
        assert k["reason"] == "no_union_groups", k
        assert trace["fallbackCount"] == 0, trace["fallbackCount"]


KERNEL_SCENARIOS = [
    (
        "kernel-tier-engaged",
        ["--pallas-dfa", "on"],
        {},
        scenario_kernel_tier_engaged,
    ),
    (
        "kernel-fault-raises",
        ["--pallas-dfa", "on"],
        {
            "LOG_PARSER_TPU_FAULTS": "kernel_raise:1.0@times=1",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_kernel_fault_raises,
    ),
]


# --------------------------------------------------- streaming scenarios


class StreamClient:
    """Raw-socket chunked-TE client for ``POST /parse/stream``. The
    stdlib ``urllib`` can neither send chunked request bodies nor read a
    response while the request is still being written, so follow-mode
    needs a hand-rolled socket: send the headers, read the immediate
    NDJSON response headers, then interleave chunk writes with frame
    reads on one connection."""

    def __init__(self, url: str, tenant: str | None = None):
        host, _, port = url.removeprefix("http://").partition(":")
        self.sock = socket.create_connection((host, int(port)), timeout=120)
        tenant_hdr = (
            f"X-Tenant: {tenant}\r\n".encode() if tenant else b""
        )
        self.sock.sendall(
            b"POST /parse/stream HTTP/1.1\r\nHost: chaos\r\n"
            + tenant_hdr
            + b"Transfer-Encoding: chunked\r\n\r\n"
        )
        buf = b""
        while b"\r\n\r\n" not in buf:
            part = self.sock.recv(65536)
            if not part:
                raise AssertionError("stream closed before response headers")
            buf += part
        head, self._buf = buf.split(b"\r\n\r\n", 1)
        self.status = int(head.split(b" ", 2)[1])
        assert self.status == 200, f"stream open -> {self.status}"

    def send(self, data: bytes) -> None:
        self.sock.sendall(b"%x\r\n" % len(data) + data + b"\r\n")

    def read_frames(self) -> list[dict]:
        """Drain NDJSON frames to server EOF (the server closes the
        connection after the terminal frame) and return them parsed."""
        buf = self._buf
        while True:
            try:
                part = self.sock.recv(65536)
            except OSError:
                break
            if not part:
                break
            buf += part
        self.sock.close()
        return [json.loads(ln) for ln in buf.splitlines() if ln.strip()]

    def finish(self) -> list[dict]:
        self.sock.sendall(b"0\r\n\r\n")  # terminating chunk closes the session
        return self.read_frames()

    def abort(self) -> None:
        self.sock.close()


def _one_final(frames: list[dict]) -> dict:
    bad = [f for f in frames if f["type"] == "error"]
    assert not bad, bad
    finals = [f for f in frames if f["type"] == "final"]
    assert len(finals) == 1 and frames[-1] is finals[0], [
        f["type"] for f in frames
    ]
    return finals[0]


def scenario_stream_device_fault_golden(srv: Server):
    """A device fault on a mid-session chunk must flip THAT session to a
    golden continuation — later chunks keep scoring, the close still
    produces a ``final`` frame, and the client never sees the fault."""
    assert post(srv.url)[0] == 200  # burns the after=1 skip deterministically
    c = StreamClient(srv.url)
    c.send(b"INFO stream boot\n")  # device eval #2: the armed fault fires here
    c.send(b"java.lang.OutOfMemoryError: heap\n")
    final = _one_final(c.finish())
    assert final["result"]["summary"]["significantEvents"] >= 1, final
    _, trace = get(srv.url, "/trace/last")
    st = trace["stream"]
    assert st["goldenContinuations"] == 1, st
    assert st["sessionsClosed"] == 1 and st["openSessions"] == 0, st
    assert trace["faults"]["fired"]["device_raise"] == 1, trace["faults"]
    assert post(srv.url)[0] == 200  # and the device path itself is fine


def scenario_stream_poison_kill(srv: Server):
    """A keyed poison chunk kills exactly its own session: a structured
    ``error`` frame with reason ``poison``, while the server — and a
    parallel fresh session — keep serving."""
    assert post(srv.url)[0] == 200  # no marker in PAYLOAD: must not fire
    c = StreamClient(srv.url)
    c.send(b"INFO clean chunk\n")
    c.send(b"POISON-PILL marker line\n")  # match= key: fires on this chunk only
    frames = c.read_frames()  # the server ends the response after the kill
    assert frames and frames[-1]["type"] == "error", frames
    assert frames[-1]["reason"] == "poison", frames[-1]
    c2 = StreamClient(srv.url)  # blast radius: one session, not the server
    c2.send(b"java.lang.OutOfMemoryError: heap\n")
    final = _one_final(c2.finish())
    assert final["result"]["summary"]["significantEvents"] >= 1, final
    assert post(srv.url)[0] == 200
    _, trace = get(srv.url, "/trace/last")
    st = trace["stream"]
    assert st["poisonKills"] == 1 and st["sessionsKilled"] >= 1, st
    assert st["openSessions"] == 0, st


def scenario_stream_reload_rebase(srv: Server):
    """A hot pattern reload landing between chunks of an open session:
    the next chunk re-bases the session onto the swapped banks (the
    reload never waits on idle sessions — quiesce counts active calls,
    not open sessions) and the session still closes with a final."""
    assert post(srv.url)[0] == 200
    c = StreamClient(srv.url)
    c.send(b"INFO stream warm\n")
    status, body = post_raw(srv.url, "/patterns/reload", b"")
    assert status == 200 and body["epoch"] == 1, (status, body)
    c.send(b"java.lang.OutOfMemoryError: heap\n")  # first post-swap chunk
    final = _one_final(c.finish())
    assert final["result"]["summary"]["significantEvents"] >= 1, final
    _, trace = get(srv.url, "/trace/last")
    st = trace["stream"]
    assert st["sessionsRebased"] >= 1, st
    assert st["sessionsClosed"] == 1 and st["openSessions"] == 0, st
    assert trace["reload"]["epoch"] == 1, trace["reload"]


def scenario_stream_ttl_reap(srv: Server):
    """Sessions abandoned mid-stream under --stream-ttl-s 1 are reaped
    while concurrent blob traffic runs: their admission slots release
    (gate inflight back to 0) and the server stays healthy."""
    c1, c2 = StreamClient(srv.url), StreamClient(srv.url)
    c1.send(b"INFO abandoned tail")
    c2.send(b"INFO abandoned tail two")
    burst = Burst(srv.url, 4)  # reap must land under live parse load
    codes = sorted(s for s, _ in burst.join(timeout=120))
    assert codes == [200] * 4, codes
    trace = _poll_trace(
        srv.url, lambda t: t.get("stream", {}).get("sessionsReaped", 0) >= 2
    )
    st = trace["stream"]
    assert st["openSessions"] == 0, st
    assert trace["admission"]["inflight"] == 0, trace["admission"]
    c1.abort()
    c2.abort()
    assert post(srv.url)[0] == 200


STREAMING_SCENARIOS = [
    (
        "stream-device-fault-golden",
        [],
        {
            "LOG_PARSER_TPU_FAULTS": "device_raise:1.0@after=1@times=1",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_stream_device_fault_golden,
    ),
    (
        "stream-poison-kill",
        [],
        {
            "LOG_PARSER_TPU_FAULTS": "quarantine_raise:1.0@match=POISON-PILL",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_stream_poison_kill,
    ),
    (
        "stream-reload-rebase",
        [],
        {},
        scenario_stream_reload_rebase,
    ),
    (
        "stream-ttl-reap",
        ["--stream-ttl-s", "1"],
        {},
        scenario_stream_ttl_reap,
    ),
]


# ------------------------------------------------------- state scenarios


def post_raw(url: str, path: str, data: bytes, timeout: float = 60.0,
             headers: dict | None = None):
    req = urllib.request.Request(
        url + path,
        data=data,
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _final_scores(body: dict) -> list:
    return [
        (ev.get("lineNumber"), ev.get("matchedPattern", {}).get("id"),
         ev.get("score"))
        for ev in body.get("events", [])
    ]


def scenario_state_kill9_replay():
    """Crash-recovery parity, operator-grade: a server hard-killed after
    3 requests and restarted on the same --state-dir must end (after 2
    more) with the same frequency stats and the same last-response scores
    as one uninterrupted server that took all 5."""
    with tempfile.TemporaryDirectory(prefix="chaos_state_") as tmp:
        crash_dir = os.path.join(tmp, "crash")
        control_dir = os.path.join(tmp, "control")

        srv = Server("state-kill9-a", ["--state-dir", crash_dir], {})
        srv.wait_ready()
        for _ in range(3):
            assert post(srv.url)[0] == 200
        srv.proc.kill()  # SIGKILL: no drain, no final snapshot
        srv.proc.wait(30)
        log_a = srv.log.name

        srv2 = Server("state-kill9-b", ["--state-dir", crash_dir], {})
        try:
            srv2.wait_ready()
            _, trace = get(srv2.url, "/trace/last")
            j = trace["journal"]
            # the kill-9 tail was replayed (or already folded into the
            # boot snapshot of run A — either way nothing was lost)
            assert j["stateDir"] == crash_dir, j
            for _ in range(1):
                assert post(srv2.url)[0] == 200
            status, last_body, _ = post(srv2.url)
            assert status == 200
            _, crashed_stats = get(srv2.url, "/frequency/stats")
        finally:
            srv2.stop()

        control = Server("state-kill9-control", ["--state-dir", control_dir], {})
        try:
            control.wait_ready()
            for _ in range(4):
                assert post(control.url)[0] == 200
            status, control_body, _ = post(control.url)
            assert status == 200
            _, control_stats = get(control.url, "/frequency/stats")
        finally:
            control.stop()

        assert crashed_stats == control_stats, (crashed_stats, control_stats)
        assert _final_scores(last_body) == _final_scores(control_body), (
            last_body, control_body
        )
        for path in (log_a, srv2.log.name, control.log.name):
            try:
                os.unlink(path)
            except OSError:
                pass


def scenario_state_torn_tail():
    """A crash mid-append leaves half a frame as the WAL's final bytes.
    The fault writes exactly that (then wedges the journal so it stays
    final); the restart must quarantine the torn bytes, replay every
    whole record, and serve."""
    with tempfile.TemporaryDirectory(prefix="chaos_state_") as tmp:
        state_dir = os.path.join(tmp, "state")
        srv = Server(
            "state-torn-a",
            ["--state-dir", state_dir, "--snapshot-every", "100000"],
            {
                # 3rd append (request 3's match record) is written torn
                "LOG_PARSER_TPU_FAULTS": "journal_torn_raise@after=2",
                "LOG_PARSER_TPU_FAULT_SEED": "42",
            },
        )
        srv.wait_ready()
        for _ in range(4):
            assert post(srv.url)[0] == 200
        srv.proc.kill()
        srv.proc.wait(30)
        log_a = srv.log.name

        srv2 = Server("state-torn-b", ["--state-dir", state_dir], {})
        try:
            srv2.wait_ready()
            assert os.path.exists(os.path.join(state_dir, "journal.wal.torn"))
            _, trace = get(srv2.url, "/trace/last")
            assert trace["journal"]["tornTails"] == 1, trace["journal"]
            assert trace["journal"]["healthy"] is True, trace["journal"]
            assert post(srv2.url)[0] == 200
        finally:
            srv2.stop()
        for path in (log_a, srv2.log.name):
            try:
                os.unlink(path)
            except OSError:
                pass


def scenario_state_canary_rollback(srv: Server):
    """An injected canary divergence must turn the reload into a 409 and
    leave the served results unchanged; the retry (fault budget spent)
    must succeed and bump the epoch."""
    status, before, _ = post(srv.url)
    assert status == 200
    status, body = post_raw(srv.url, "/patterns/reload", b"")
    assert status == 409, (status, body)
    assert body["stage"] == "canary", body
    _, trace = get(srv.url, "/trace/last")
    assert trace["reload"]["epoch"] == 0, trace["reload"]
    assert trace["reload"]["failures"] == 1, trace["reload"]
    # old banks still serving, scores unchanged
    status, after, _ = post(srv.url)
    assert status == 200
    assert _final_scores(after) == _final_scores(before), (after, before)
    status, body = post_raw(srv.url, "/patterns/reload", b"")
    assert status == 200, (status, body)
    assert body["epoch"] == 1, body
    assert post(srv.url)[0] == 200


def scenario_state_reload_under_load(srv: Server):
    """Hot reload racing a concurrent batched burst: every request 200,
    the reload completes, nothing wedges."""
    post(srv.url)  # warm the batch program
    burst = Burst(srv.url, 8)
    time.sleep(0.05)  # let the burst enqueue before the swap quiesces
    status, body = post_raw(srv.url, "/patterns/reload", b"")
    results = burst.join(timeout=120)
    codes = sorted(s for s, _ in results)
    assert codes == [200] * 8, codes
    assert status == 200, (status, body)
    assert body["epoch"] == 1, body
    # the swapped banks serve the next request
    assert post(srv.url)[0] == 200
    _, trace = get(srv.url, "/trace/last")
    assert trace["reload"]["epoch"] == 1, trace["reload"]
    assert trace["reload"]["failures"] == 0, trace["reload"]


# state scenarios that manage their own server lifecycle (kill/restart)
STATE_STANDALONE = [
    ("state-kill9-replay", scenario_state_kill9_replay),
    ("state-torn-tail", scenario_state_torn_tail),
]

STATE_SCENARIOS = [
    (
        "state-canary-rollback",
        [],
        {
            "LOG_PARSER_TPU_FAULTS": "reload_canary_raise@times=1",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_state_canary_rollback,
    ),
    (
        "state-reload-under-load",
        ["--batching", "on", "--batch-wait-ms", "20", "--batch-max", "8"],
        {},
        scenario_state_reload_under_load,
    ),
]


# ------------------------------------------------- distributed scenarios


_NO_CPU_MULTIPROCESS = "Multiprocess computations aren't implemented"


class DistributedPair:
    """A coordinator serve child + one follower child sharing a
    jax.distributed runtime (4 virtual CPU devices each → one 8-device
    global mesh). The coordinator owns HTTP; the follower replays
    broadcasts in follower_loop."""

    def __init__(self, name: str, coord_args: list[str], coord_env: dict):
        dist_port = free_port()
        shared = [
            "--coordinator", f"127.0.0.1:{dist_port}", "--num-processes", "2",
        ]
        base_env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
        self.follower_log = tempfile.NamedTemporaryFile(
            "wb", prefix=f"chaos_{name}_follower_", suffix=".log", delete=False
        )
        self.follower = subprocess.Popen(
            [
                sys.executable, "-m", "log_parser_tpu.serve",
                "--pattern-dir", PATTERN_DIR,
                *shared, "--process-id", "1",
            ],
            cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONUNBUFFERED": "1", **base_env},
            stdout=self.follower_log,
            stderr=subprocess.STDOUT,
        )
        self.coord = Server(
            name,
            [*shared, "--process-id", "0", *coord_args],
            {**base_env, **coord_env},
        )
        self.url = self.coord.url
        self.log = self.coord.log

    def logs_tail(self) -> str:
        out = []
        for path in (self.coord.log.name, self.follower_log.name):
            try:
                with open(path, "rb") as f:
                    out.append(f.read()[-4000:].decode(errors="replace"))
            except OSError:
                pass
        return "\n".join(out)

    def stop(self) -> None:
        self.coord.stop()
        if self.follower.poll() is None:
            try:
                self.follower.wait(30)
            except subprocess.TimeoutExpired:
                self.follower.kill()
                self.follower.wait(10)


def scenario_follower_degrade(pair: DistributedPair):
    # r1 rides the full mesh before the fault arms (after=1)
    status, body, _ = post(pair.url, timeout=60)
    assert status == 200, f"expected 200, got {status}"
    assert "degraded" not in body.get("metadata", {}), body["metadata"]

    # r2: the follower hang burns the whole broadcast budget (2s x 2) —
    # the request must still answer 200, served degraded from local chips
    status, body, _ = post(pair.url, timeout=120)
    assert status == 200, f"degraded request got {status}"
    assert body["metadata"].get("degraded") == "distributed-fallback", (
        body.get("metadata")
    )
    _, health = get(pair.url, "/health")
    assert {"name": "mesh", "status": "DEGRADED"} in health.get("checks", []), health

    # the heartbeat probe must re-admit the mesh once the hang expires
    # (times=2 budget was spent inside r2)
    deadline = time.monotonic() + 30
    mode = None
    while time.monotonic() < deadline:
        _, trace = get(pair.url, "/trace/last")
        mode = trace.get("distributed", {}).get("mode")
        if mode == "distributed":
            break
        time.sleep(0.3)
    assert mode == "distributed", f"mesh never re-admitted (mode={mode})"
    assert trace["distributed"]["broadcastTimeouts"] >= 2, trace["distributed"]
    assert trace["distributed"]["degradedRequests"] >= 1, trace["distributed"]
    assert trace["distributed"]["readmissions"] >= 1, trace["distributed"]

    # r3 is distributed again, and SIGTERM shuts BOTH processes down
    status, body, _ = post(pair.url, timeout=60)
    assert status == 200 and "degraded" not in body.get("metadata", {})
    pair.coord.proc.send_signal(signal.SIGTERM)
    pair.coord.proc.wait(60)
    assert pair.coord.proc.returncode == 0, f"rc={pair.coord.proc.returncode}"
    pair.follower.wait(60)
    assert pair.follower.returncode == 0, f"follower rc={pair.follower.returncode}"


DISTRIBUTED_SCENARIOS = [
    (
        "follower-degrade",
        [
            "--broadcast-timeout", "2", "--broadcast-retries", "1",
            "--dead-after", "2", "--heartbeat-s", "0.5",
        ],
        {
            "LOG_PARSER_TPU_FAULTS": "follower_hang:30@after=1@times=2",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_follower_degrade,
    ),
]


def _make_tenant_root(tmp: str, tenants=("acme", "globex")) -> str:
    """A tenant library root: one sub-directory per tenant, each a copy
    of the builtin pattern library (content identical on purpose — these
    scenarios pin isolation mechanics, not per-tenant pattern authoring)."""
    root = os.path.join(tmp, "tenants")
    for tid in tenants:
        shutil.copytree(PATTERN_DIR, os.path.join(root, tid))
    return root


def scenario_tenant_quota_shed():
    """One tenant's lines/s bucket empties under a concurrent burst: the
    over-quota requests get structured 429s with Retry-After while the
    burst's head (and the default tenant) are served normally."""
    with tempfile.TemporaryDirectory(prefix="chaos_tenant_") as tmp:
        root = _make_tenant_root(tmp)
        # PAYLOAD is 3 lines; lines/s 2 with the 2s burst window is a
        # 4-token bucket — exactly one concurrent request fits
        srv = Server(
            "tenant-quota-shed",
            ["--tenant-root", root, "--tenant-lines-per-s", "2"],
            {},
        )
        try:
            srv.wait_ready()
            hdr = {"X-Tenant": "acme"}
            # the burst also races first-touch resolution: one thread
            # builds acme's bank, the rest coalesce on the build event
            results = Burst(srv.url, 8, headers=hdr).join(timeout=180)
            codes = [s for s, _ in results]
            assert set(codes) <= {200, 429}, codes
            assert codes.count(200) >= 1, codes
            assert codes.count(429) >= 5, codes
            for status, hdrs in results:
                if status == 429:
                    assert int(hdrs["Retry-After"]) >= 1, hdrs
            # bucket still empty: a follow-up shows the structured body
            status, body, _ = post(srv.url, hdr)
            assert status == 429 and body["reason"] == "tenant rate", (
                status, body,
            )
            # the default tenant's own bucket is untouched by acme's shed
            assert post(srv.url)[0] == 200
            _, trace = get(srv.url, "/trace/last")
            assert trace["admission"]["shedTenant"] >= 5, trace["admission"]
            quota = trace["tenants"]["perTenant"]["acme"]["quota"]
            assert quota["shedRate"] >= 5, quota
        finally:
            srv.stop()


def scenario_tenant_evict_rebuild():
    """A bank budget sized for ~1.5 tenants: the second tenant's arrival
    LRU-evicts the first, the first's return rebuilds it — all while a
    concurrent default-tenant burst keeps answering 200 (builds happen
    outside the registry lock, so nobody stalls behind a compile)."""
    with tempfile.TemporaryDirectory(prefix="chaos_tenant_") as tmp:
        root = _make_tenant_root(tmp)
        # measure one bank's resident bytes off a probe server — the
        # budget flag must land between 1x and 2x of a bank to force
        # eviction on the second tenant without thrashing the first
        probe = Server("tenant-evict-probe", ["--tenant-root", root], {})
        try:
            probe.wait_ready()
            assert post(probe.url, {"X-Tenant": "acme"})[0] == 200
            _, trace = get(probe.url, "/trace/last")
            bank_mb = (
                trace["tenants"]["perTenant"]["acme"]["bankBytes"] / 2**20
            )
        finally:
            probe.stop()
        srv = Server(
            "tenant-evict-rebuild",
            ["--tenant-root", root,
             "--tenant-budget-mb", f"{bank_mb * 1.5:.4f}"],
            {},
        )
        try:
            srv.wait_ready()
            assert post(srv.url, {"X-Tenant": "acme"})[0] == 200
            burst = Burst(srv.url, 4)  # default-tenant load rides along
            assert post(srv.url, {"X-Tenant": "globex"})[0] == 200  # evicts
            assert post(srv.url, {"X-Tenant": "acme"})[0] == 200  # rebuilds
            codes = sorted(s for s, _ in burst.join(timeout=180))
            assert codes == [200] * 4, codes
            _, trace = get(srv.url, "/trace/last")
            t = trace["tenants"]
            assert t["evicted"] >= 1, t
            assert t["rebuilds"] >= 1, t
            assert t["residentBankMb"] <= t["budgetMb"] + bank_mb + 1, t
        finally:
            srv.stop()


def scenario_tenant_reload_isolated():
    """A hot reload scoped to tenant A races a burst of tenant-B traffic:
    the quiesce runs on A's engine alone, so every B request answers 200;
    A's reloadEpoch bumps while B's and the default tenant's stay 0."""
    with tempfile.TemporaryDirectory(prefix="chaos_tenant_") as tmp:
        root = _make_tenant_root(tmp)
        srv = Server("tenant-reload-isolated", ["--tenant-root", root], {})
        try:
            srv.wait_ready()
            assert post(srv.url, {"X-Tenant": "acme"})[0] == 200
            assert post(srv.url, {"X-Tenant": "globex"})[0] == 200
            burst = Burst(srv.url, 6, headers={"X-Tenant": "globex"})
            status, body = post_raw(
                srv.url, "/patterns/reload", b"",
                headers={"X-Tenant": "acme"},
            )
            codes = sorted(s for s, _ in burst.join(timeout=180))
            assert codes == [200] * 6, codes
            assert status == 200 and body["epoch"] == 1, (status, body)
            _, trace = get(srv.url, "/trace/last")
            per = trace["tenants"]["perTenant"]
            assert per["acme"]["reloadEpoch"] == 1, per["acme"]
            assert per["globex"]["reloadEpoch"] == 0, per["globex"]
            assert per["default"]["reloadEpoch"] == 0, per["default"]
        finally:
            srv.stop()


# tenant scenarios manage their own server lifecycle (the library root
# must exist before the Server's flag list can reference it)
TENANT_STANDALONE = [
    ("tenant-quota-shed", scenario_tenant_quota_shed),
    ("tenant-evict-rebuild", scenario_tenant_evict_rebuild),
    ("tenant-reload-isolated", scenario_tenant_reload_isolated),
]


# ------------------------------------------------- migrate group scenarios


def _migrate_pair(tmp: str, src_name: str, dst_name: str,
                  src_env: dict | None = None,
                  src_flags: list | None = None):
    """Two serve processes sharing one tenant library root (the bank
    content-hash check requires identical pattern config on both sides),
    each with its own --state-dir for WALs and migration journals."""
    root = _make_tenant_root(tmp)
    src = Server(
        src_name,
        ["--tenant-root", root,
         "--state-dir", os.path.join(tmp, "src_state"),
         *(src_flags or [])],
        src_env or {},
    )
    dst = Server(
        dst_name,
        ["--tenant-root", root,
         "--state-dir", os.path.join(tmp, "dst_state")],
        {},
    )
    return src, dst


def scenario_migrate_live_cutover():
    """The happy path end to end: acme migrates from source to target
    over HTTP; afterwards the source answers acme with a 307 (Location +
    Retry-After) while the target serves it with the migrated frequency
    history applied."""
    with tempfile.TemporaryDirectory(prefix="chaos_migrate_") as tmp:
        src, dst = _migrate_pair(tmp, "migrate-src", "migrate-dst")
        try:
            src.wait_ready()
            dst.wait_ready()
            hdr = {"X-Tenant": "acme"}
            for _ in range(2):  # build frequency history worth moving
                assert post(src.url, hdr)[0] == 200
            status, body = post_raw(
                src.url, "/admin/migrate",
                json.dumps({"tenant": "acme", "target": dst.url}).encode(),
            )
            assert status == 200 and body["outcome"] == "completed", (
                status, body,
            )
            # the source now 307-forwards acme with the redirect envelope
            code, fbody, fhdrs = post(src.url, hdr)
            assert code == 307, (code, fbody)
            assert fhdrs["Location"].startswith(dst.url), fhdrs
            assert int(fhdrs["Retry-After"]) >= 1, fhdrs
            assert dst.url in fbody["location"], fbody
            # ...while the target owns it (and the default tenant on the
            # source is untouched)
            assert post(dst.url, hdr)[0] == 200
            assert post(src.url)[0] == 200
            _, strace = get(src.url, "/trace/last")
            m = strace["migration"]
            assert m["completed"] == 1 and m["forwards"] == 1, m
            assert m["aborted"] == 0, m
            _, dtrace = get(dst.url, "/trace/last")
            dm = dtrace["migration"]
            assert dm["staged"] == 1 and dm["activated"] == 1, dm
        finally:
            src.stop()
            dst.stop()


def scenario_migrate_crash_mid_export():
    """The ``migrate_export`` fault fires under the quiesce gate: the
    migration aborts with a structured 409, the source keeps the tenant
    (no forward, still 200), and the abort is durable — a journaled
    ABORT record, not a wedge."""
    with tempfile.TemporaryDirectory(prefix="chaos_migrate_") as tmp:
        root = _make_tenant_root(tmp)
        srv = Server(
            "migrate-crash-export",
            ["--tenant-root", root,
             "--state-dir", os.path.join(tmp, "state")],
            {"LOG_PARSER_TPU_FAULTS": "migrate_export_raise@times=1"},
        )
        try:
            srv.wait_ready()
            hdr = {"X-Tenant": "acme"}
            assert post(srv.url, hdr)[0] == 200
            status, body = post_raw(
                srv.url, "/admin/migrate",
                json.dumps({"tenant": "acme",
                            "target": "http://127.0.0.1:9"}).encode(),
            )
            assert status == 409, (status, body)
            # the source still owns acme: served locally, no forward
            assert post(srv.url, hdr)[0] == 200
            _, trace = get(srv.url, "/trace/last")
            m = trace["migration"]
            assert m["aborted"] == 1 and m["forwards"] == 0, m
            assert m["completed"] == 0, m
            assert trace["faults"]["fired"]["migrate_export_raise"] == 1, (
                trace["faults"]
            )
        finally:
            srv.stop()


def scenario_migrate_crash_pre_cutover():
    """The ``migrate_cutover`` fault fires AFTER the target staged the
    bundle but before the commit record: the source aborts and keeps
    serving; the target's staged-but-never-activated copy must never
    apply (single-owner invariant)."""
    with tempfile.TemporaryDirectory(prefix="chaos_migrate_") as tmp:
        src, dst = _migrate_pair(
            tmp, "migrate-precut-src", "migrate-precut-dst",
            src_env={
                "LOG_PARSER_TPU_FAULTS": "migrate_cutover_raise@times=1"
            },
        )
        try:
            src.wait_ready()
            dst.wait_ready()
            hdr = {"X-Tenant": "acme"}
            assert post(src.url, hdr)[0] == 200
            status, body = post_raw(
                src.url, "/admin/migrate",
                json.dumps({"tenant": "acme", "target": dst.url}).encode(),
            )
            assert status == 409, (status, body)
            # source still owns: 200, no forward installed
            assert post(src.url, hdr)[0] == 200
            _, strace = get(src.url, "/trace/last")
            m = strace["migration"]
            assert m["aborted"] == 1 and m["forwards"] == 0, m
            # the target staged the bundle but never activated it
            _, dtrace = get(dst.url, "/trace/last")
            dm = dtrace["migration"]
            assert dm["staged"] == 1 and dm["activated"] == 0, dm
            assert dm["stagedNow"] == 1, dm
        finally:
            src.stop()
            dst.stop()


def scenario_migrate_drain_under_burst():
    """POST /admin/drain while a default-tenant burst is in flight: the
    drain closes every resident tenant under the deadline, /q/health
    flips to a DRAINING 503 for the LBs, the burst sees only 200s (head)
    or structured 503s (tail), and SIGTERM afterwards exits clean."""
    with tempfile.TemporaryDirectory(prefix="chaos_migrate_") as tmp:
        root = _make_tenant_root(tmp)
        srv = Server(
            "migrate-drain-burst",
            ["--tenant-root", root,
             "--state-dir", os.path.join(tmp, "state"),
             "--drain-deadline-s", "15"],
            {},
        )
        try:
            srv.wait_ready()
            assert post(srv.url, {"X-Tenant": "acme"})[0] == 200
            assert post(srv.url, {"X-Tenant": "globex"})[0] == 200
            burst = Burst(srv.url, 6)
            status, body = post_raw(srv.url, "/admin/drain", b"{}")
            assert status == 200, (status, body)
            assert sorted(body["closed"]) == ["acme", "globex"], body
            assert body["elapsedS"] <= 15, body
            codes = [s for s, _ in burst.join(timeout=120)]
            assert set(codes) <= {200, 503}, codes
            hstatus, health = get(srv.url, "/q/health")
            assert hstatus == 503 and health["status"] == "DRAINING", (
                hstatus, health,
            )
            assert any(
                c["name"] == "drain" and c["status"] == "DRAINING"
                for c in health["checks"]
            ), health
            _, trace = get(srv.url, "/trace/last")
            d = trace["migration"]["drain"]
            assert d["draining"] == 1 and d["tenantsClosed"] == 2, d
        finally:
            srv.stop(expect_zero=True)


def scenario_migrate_stream_handoff():
    """A live follow-mode session is open on the migrating tenant: the
    cutover must not hang on it — across processes the session closes
    with an explicit ``error`` frame naming the new owner, and the
    tenant's blob traffic 307-forwards."""
    with tempfile.TemporaryDirectory(prefix="chaos_migrate_") as tmp:
        src, dst = _migrate_pair(tmp, "migrate-stream-src",
                                 "migrate-stream-dst")
        try:
            src.wait_ready()
            dst.wait_ready()
            hdr = {"X-Tenant": "acme"}
            assert post(src.url, hdr)[0] == 200
            c = StreamClient(src.url, tenant="acme")
            c.send(b"INFO pinned session\n")
            status, body = post_raw(
                src.url, "/admin/migrate",
                json.dumps({"tenant": "acme", "target": dst.url}).encode(),
            )
            assert status == 200 and body["outcome"] == "completed", (
                status, body,
            )
            assert body["sessionsClosed"] == 1, body
            # the handler thread is blocked reading chunks; the next
            # chunk lands on the killed session and flushes its terminal
            # error frame back down this connection
            c.send(b"INFO post-cutover chunk\n")
            frames = c.read_frames()
            assert frames and frames[-1]["type"] == "error", frames
            assert frames[-1]["reason"] == "migrated", frames[-1]
            assert dst.url in frames[-1]["message"], frames[-1]
            assert post(src.url, hdr)[0] == 307
            assert post(dst.url, hdr)[0] == 200
        finally:
            src.stop()
            dst.stop()


MIGRATE_STANDALONE = [
    ("migrate-live-cutover", scenario_migrate_live_cutover),
    ("migrate-crash-mid-export", scenario_migrate_crash_mid_export),
    ("migrate-crash-pre-cutover", scenario_migrate_crash_pre_cutover),
    ("migrate-drain-under-burst", scenario_migrate_drain_under_burst),
    ("migrate-stream-handoff", scenario_migrate_stream_handoff),
]


# Replica group (``--group replica``; warm-standby replication + fenced
# failover — docs/OPS.md "Warm-standby replication & failover"): real
# primary/standby pairs over HTTP; where a drill needs a dead primary it
# dies by SIGKILL, so promotion must work from the epoch journal and the
# standby's own re-journaled WAL alone.


def _replica_pair(tmp: str, prefix: str, failover_s: float | None = None):
    """A primary continuously shipping to a warm standby. The primary
    boots first and must be ready before the standby exists: an armed
    standby starts probing immediately, and primary boot latency must
    never be counted as primary death."""
    root = _make_tenant_root(tmp)
    a_port, b_port = free_port(), free_port()
    primary = Server(
        f"{prefix}-primary",
        ["--tenant-root", root,
         "--state-dir", os.path.join(tmp, "a_state"),
         "--replica-target", f"http://127.0.0.1:{b_port}"],
        {}, port=a_port,
    )
    primary.wait_ready()
    flags = ["--tenant-root", root,
             "--state-dir", os.path.join(tmp, "b_state"),
             "--replica-of", f"http://127.0.0.1:{a_port}"]
    if failover_s is not None:
        flags += ["--failover-after-s", str(failover_s)]
    standby = Server(f"{prefix}-standby", flags, {}, port=b_port)
    standby.wait_ready()
    return primary, standby


def _applied_records(url: str) -> int:
    _, trace = get(url, "/trace/last")
    rep = trace.get("replication") or {}
    return int(rep.get("appliedRecords", 0))


def scenario_replica_failover_kill9():
    """The acceptance drill end to end: a pair ships live WAL (the lag
    families are on /metrics), the primary dies by SIGKILL, the armed
    supervisor promotes the standby, and the standby serves the
    tenant's replicated history un-fenced."""
    with tempfile.TemporaryDirectory(prefix="chaos_replica_") as tmp:
        primary, standby = _replica_pair(tmp, "replica-kill9",
                                         failover_s=3.0)
        try:
            hdr = {"X-Tenant": "acme"}
            assert post(primary.url, hdr)[0] == 200
            assert post(primary.url)[0] == 200  # default tenant too
            # the standby is fenced while its primary lives
            code, _, fhdrs = post(standby.url, hdr)
            assert code == 307, code
            assert fhdrs["Location"].startswith(primary.url), fhdrs
            # shipping is continuous: both tenants' frames land and are
            # re-journaled on the standby
            _poll_trace(
                standby.url,
                lambda t: (t.get("replication") or {}).get(
                    "appliedRecords", 0) >= 2,
                timeout=45.0,
            )
            _, text = get_text(primary.url, "/metrics")
            assert "logparser_replication_lag_bytes" in text, (
                "lag families missing from /metrics"
            )
            assert "logparser_replication_lag_records" in text
            primary.proc.kill()  # SIGKILL: no drain, no goodbye
            primary.proc.wait(10)
            trace = _poll_trace(
                standby.url,
                lambda t: (t.get("replication") or {}).get("role")
                == "primary",
                timeout=30.0,
            )
            rep = trace["replication"]
            assert rep["promotions"] >= 1 and rep["epoch"] >= 1, rep
            # the supervisor fired and disarmed itself: it counted the
            # primary down for the full threshold before promoting
            fo = rep["failover"]
            assert fo["failures"] >= 1 and fo["downS"] >= 3.0, fo
            # the fence is lifted: the replicated history serves here now
            assert post(standby.url, hdr)[0] == 200
            assert post(standby.url)[0] == 200
            _, text = get_text(standby.url, "/metrics")
            assert "logparser_replication_promotions_total" in text
        finally:
            primary.stop()
            standby.stop()


def scenario_replica_stale_primary_demotes():
    """Promote the standby while the primary is still alive — the
    operator error split-brain fencing exists for. The stale primary's
    next shipped batch is refused with the higher epoch, it demotes
    itself durably, and its client traffic 307-forwards to the new
    owner instead of double-serving."""
    with tempfile.TemporaryDirectory(prefix="chaos_replica_") as tmp:
        primary, standby = _replica_pair(tmp, "replica-stale")
        try:
            hdr = {"X-Tenant": "acme"}
            assert post(primary.url, hdr)[0] == 200
            _poll_trace(
                standby.url,
                lambda t: (t.get("replication") or {}).get(
                    "appliedRecords", 0) >= 1,
                timeout=45.0,
            )
            status, body = post_raw(standby.url, "/admin/promote",
                                    b'{"reason":"drill"}')
            assert status == 200 and body["status"] == "promoted", (
                status, body,
            )
            assert body["epoch"] >= 1, body
            # new traffic on the stale primary journals fresh frames; its
            # pump ships them with the old epoch and gets refused
            assert post(primary.url, hdr)[0] in (200, 307)
            trace = _poll_trace(
                primary.url,
                lambda t: (t.get("replication") or {}).get("role")
                == "standby",
                timeout=30.0,
            )
            rep = trace["replication"]
            assert rep["demotions"] >= 1, rep
            assert rep["epoch"] >= body["epoch"], rep
            # fenced: the loser forwards to the winner
            code, _, fhdrs = post(primary.url, hdr)
            assert code == 307, code
            assert fhdrs["Location"].startswith(standby.url), fhdrs
            assert post(standby.url, hdr)[0] == 200
        finally:
            primary.stop()
            standby.stop()


def scenario_replica_lagging_promotion():
    """SIGKILL the primary with an unshipped WAL tail, then promote by
    hand: the standby serves the acked prefix — the documented
    state-loss bound — and the promotion is journaled (idempotent on a
    second POST)."""
    with tempfile.TemporaryDirectory(prefix="chaos_replica_") as tmp:
        primary, standby = _replica_pair(tmp, "replica-lag")
        try:
            hdr = {"X-Tenant": "acme"}
            assert post(primary.url, hdr)[0] == 200
            _poll_trace(
                standby.url,
                lambda t: (t.get("replication") or {}).get(
                    "appliedRecords", 0) >= 1,
                timeout=45.0,
            )
            acked = _applied_records(standby.url)
            # pile on a tail and kill before the 0.2s pump can ship all
            # of it — some of these frames (and some of these requests)
            # die with the primary, which is the point
            def fire():
                try:
                    post(primary.url, hdr, timeout=10)
                except OSError:
                    pass  # connection died under SIGKILL
            threads = [threading.Thread(target=fire) for _ in range(4)]
            for t in threads:
                t.start()
            primary.proc.kill()
            primary.proc.wait(10)
            for t in threads:
                t.join(30)
            assert all(not t.is_alive() for t in threads), "burst stuck"
            status, body = post_raw(standby.url, "/admin/promote",
                                    b'{"reason":"primary dead"}')
            assert status == 200 and body["status"] == "promoted", (
                status, body,
            )
            # idempotent re-promote: already primary, same epoch
            status2, body2 = post_raw(standby.url, "/admin/promote", b"{}")
            assert status2 == 200 and body2["status"] == "primary", (
                status2, body2,
            )
            assert body2["epoch"] == body["epoch"], (body, body2)
            # the acked prefix survived the failover and serves
            assert _applied_records(standby.url) >= acked
            assert post(standby.url, hdr)[0] == 200
            _, trace = get(standby.url, "/trace/last")
            rep = trace["replication"]
            assert rep["role"] == "primary" and rep["promotions"] >= 1, rep
        finally:
            primary.stop()
            standby.stop()


REPLICA_STANDALONE = [
    ("replica-failover-kill9", scenario_replica_failover_kill9),
    ("replica-stale-primary-demotes", scenario_replica_stale_primary_demotes),
    ("replica-lagging-promotion", scenario_replica_lagging_promotion),
]


def scenario_miner_tap_overflow(srv: Server):
    """A wedged miner worker (miner_hang:inf) under a tiny tap capacity:
    the bounded queue fills, further novel lines become DROPS — counted
    on /trace/last, invisible to the hot path (every request still 200,
    nothing blocks behind the dead consumer)."""
    for r in range(6):
        lines = "\n".join(
            f"chaosnovel{r}x{i} widget rebalance pass={r}.{i}" for i in range(12)
        )
        status, body, _ = post_logs(srv.url, lines)
        assert status == 200, (status, body)
    trace = _poll_trace(
        srv.url, lambda t: t.get("miner", {}).get("dropped", 0) >= 1
    )
    m = trace["miner"]
    assert m["queued"] <= 4, m  # capacity env below
    assert m["tapped"] <= 4, m  # nothing drained: worker is wedged
    assert m["clusters"] == 0, m  # the consumer really is dead
    # the hot path after saturation: still instant 200s
    assert post_logs(srv.url, "one more\nplain line")[0] == 200


MINER_SCENARIOS = [
    (
        "miner-tap-overflow",
        ["--miner", "on"],
        {
            "LOG_PARSER_TPU_FAULTS": "miner_hang:inf",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
            "LOG_PARSER_TPU_MINER_TAP_CAPACITY": "4",
        },
        scenario_miner_tap_overflow,
    ),
]


# ------------------------------------------------- observability scenarios
#
# Obs group (``--group obs``; the fleet observability plane — docs/OPS.md
# "Observability"): /metrics stays live and monotone while the device
# path is faulting; the slow-request ring captures the faulted request by
# its propagated id; sustained availability burn flips the /q/health
# ``slo`` check DEGRADED and it recovers once the error cells age out of
# every window.


def get_text(url: str, path: str):
    """Raw-text GET — /metrics is Prometheus exposition, not JSON."""
    with urllib.request.urlopen(url + path, timeout=10) as resp:
        return resp.status, resp.read().decode()


def _metric_total(text: str, name: str) -> float | None:
    """Sum every sample of one metric family across its label sets."""
    total, found = 0.0, False
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        head = line.split(" ", 1)[0]
        if head.split("{", 1)[0] == name:
            total += float(line.rsplit(" ", 1)[1])
            found = True
    return total if found else None


def scenario_obs_metrics_monotone(srv: Server):
    status, text = get_text(srv.url, "/metrics")
    assert status == 200, status
    assert "# TYPE logparser_requests_total counter" in text, "missing TYPE"
    before = _metric_total(text, "logparser_requests_total") or 0.0
    statuses = [post(srv.url)[0] for _ in range(8)]
    assert statuses == [200] * 8, statuses  # faults fall back to golden
    status, text = get_text(srv.url, "/metrics")
    assert status == 200, "metrics endpoint died under device faults"
    assert 'le="+Inf"' in text, "histogram without +Inf bucket"
    after = _metric_total(text, "logparser_requests_total")
    assert after is not None and after >= before + 8, (before, after)
    fallbacks = _metric_total(text, "logparser_fallback_total")
    assert fallbacks and fallbacks >= 1, f"seeded p=0.5 never fired: {fallbacks}"
    # registry and /trace/last read the same counters — no dual books
    _, trace = get(srv.url, "/trace/last")
    assert trace["fallbackCount"] == fallbacks, (trace["fallbackCount"], fallbacks)


def scenario_obs_slow_ring_capture(srv: Server):
    # request 1 eats the injected 0.5 s device stall (plus first-compile
    # time) — far over the 250 ms bar; its propagated id must land in the
    # slow ring and survive later fast traffic
    status, _, hdrs = post(srv.url, headers={"X-Request-Id": "slowpoke-1"})
    assert status == 200, status
    assert hdrs.get("X-Request-Id") == "slowpoke-1", hdrs
    for _ in range(3):
        assert post(srv.url)[0] == 200
    _, recent = get(srv.url, "/trace/recent?n=10")
    slow_ids = [e["requestId"] for e in recent["slow"]]
    assert "slowpoke-1" in slow_ids, slow_ids
    assert recent["ring"]["slowCaptured"] >= 1, recent["ring"]
    assert len(recent["requests"]) == 4, recent["requests"]


def scenario_obs_slo_burn_flip(srv: Server):
    # 6 injected transport 500s in one second: error frac 1.0 against a
    # 0.1 budget burns 10x on both (2 s / 4 s) windows -> DEGRADED
    statuses = [post(srv.url)[0] for _ in range(6)]
    assert statuses == [500] * 6, statuses
    _, health = get(srv.url, "/q/health")
    slo = next(c for c in health.get("checks", []) if c["name"] == "slo")
    assert slo["status"] == "DEGRADED", slo
    assert "availability" in slo["burning"], slo
    # fault spec is exhausted (@times=6): traffic is healthy again; the
    # error cells age out of the 4 s window and the check recovers
    deadline = time.monotonic() + 15
    recovered = False
    while time.monotonic() < deadline:
        assert post(srv.url)[0] == 200
        _, health = get(srv.url, "/q/health")
        checks = health.get("checks", [])
        slo = next((c for c in checks if c["name"] == "slo"), None)
        if slo is None or slo["status"] == "UP":
            recovered = True
            break
        time.sleep(0.5)
    assert recovered, f"slo check never recovered: {health}"


OBS_SCENARIOS = [
    (
        "obs-metrics-monotone",
        # cache off so every request reaches the faulted device site
        ["--line-cache-mb", "0"],
        {
            "LOG_PARSER_TPU_FAULTS": "device_raise:0.5",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_obs_metrics_monotone,
    ),
    (
        "obs-slow-ring-capture",
        ["--trace-slow-ms", "250"],
        {
            "LOG_PARSER_TPU_FAULTS": "device_slow:0.5@times=1",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_obs_slow_ring_capture,
    ),
    (
        "obs-slo-burn-flip",
        ["--slo-availability", "0.9"],
        {
            "LOG_PARSER_TPU_SLO_WINDOWS_S": "2,4",
            "LOG_PARSER_TPU_FAULTS": "http_raise:1.0@times=6",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_obs_slo_burn_flip,
    ),
]


# Spans group (``--group spans``; causal span tracing — docs/OPS.md
# "Span tracing & utilization accounting"): a faulted device dispatch
# records its span — carrying the fault site — on the flush trace
# before bisection retries, and the sampling drop path never orphans a
# staged child span while force-kept flush traces still commit.


def _poll_spans(url: str, pred, timeout: float = 30.0) -> dict:
    """Poll GET /trace/spans until ``pred(body)`` — the flush trace
    commits on the scheduler thread a beat after the member responses
    return, so assertions on it must wait it out."""
    deadline = time.monotonic() + timeout
    body = {}
    while time.monotonic() < deadline:
        status, body = get(url, "/trace/spans?n=64")
        assert status == 200, status
        if pred(body):
            return body
        time.sleep(0.25)
    raise AssertionError(f"span predicate never held: {body}")


def scenario_spans_fault_site(srv: Server):
    post(srv.url)  # warm: one device call burns the fault's after=1
    results = Burst(srv.url, 4).join(timeout=120)
    codes = sorted(s for s, _ in results)
    assert codes == [200] * 4, codes  # bisection/golden absorbed the fault

    def _faulted_flush_closed(body):
        flushes = [t for t in body["traces"] if t["name"] == "flush"]
        return any(
            "error" in (s.get("attrs") or {})
            for t in flushes for s in t["spans"] if s["name"] == "dispatch"
        ) and all(
            any(s["name"] == "demux" for s in t["spans"]) for t in flushes
        )

    body = _poll_spans(srv.url, _faulted_flush_closed)
    flushes = [t for t in body["traces"] if t["name"] == "flush"]
    # the faulted dispatch recorded its span with the failure attr, and
    # the SAME flush trace still closed with its demux span — a fault is
    # a recorded child of the tree, never a hole in it
    faulted = [
        t for t in flushes
        if any(
            "error" in (s.get("attrs") or {})
            for s in t["spans"] if s["name"] == "dispatch"
        )
    ]
    assert faulted, [t["name"] for t in body["traces"]]
    assert any(s["name"] == "demux" for s in faulted[0]["spans"]), faulted[0]
    # causality survives the fault: flush roots still link member request
    # traces, and a member request back-links a flush trace
    linked = {
        ln["traceId"]
        for t in flushes for ln in (t["spans"][0].get("links") or [])
    }
    assert linked, flushes
    requests = [t for t in body["traces"] if t["name"] == "request"]
    flush_ids = {t["traceId"] for t in flushes}
    assert any(
        ln["traceId"] in flush_ids
        for t in requests for ln in (t["spans"][0].get("links") or [])
    ), requests
    assert body["store"]["staged"] == 0, body["store"]


def scenario_spans_sample_drop(srv: Server):
    post(srv.url)  # warm compile off the clock
    results = Burst(srv.url, 4).join(timeout=120)
    codes = sorted(s for s, _ in results)
    assert codes == [200] * 4, codes
    # flush traces are rare and force-kept: they commit at sample 0
    body = _poll_spans(
        srv.url, lambda b: any(t["name"] == "flush" for t in b["traces"])
    )
    names = [t["name"] for t in body["traces"]]
    # ... while every request trace was head-sampled away (slow bar
    # lifted out of reach so the always-on slow path cannot rescue them)
    assert "request" not in names, names
    store = body["store"]
    assert store["droppedTraces"] >= 5, store
    # the drop popped each request's staged enqueue/admission children
    # with it — a dropped sample never orphans a staged span
    assert store["staged"] == 0, store


SPANS_SCENARIOS = [
    (
        "spans-fault-site",
        # cache off: identical chaos payloads would be full line-cache
        # hits after the warm post and the flush would never reach the
        # faulted device dispatch
        BATCHER_FLAGS + ["--line-cache-mb", "0"],
        {
            "LOG_PARSER_TPU_FAULTS": "device_raise@times=1@after=1",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_spans_fault_site,
    ),
    (
        "spans-sample-drop",
        BATCHER_FLAGS + ["--trace-sample", "0", "--trace-slow-ms", "60000"],
        {},
        scenario_spans_sample_drop,
    ),
]


def _miner_engine(curated_regex: str, mode: str = "auto"):
    """In-process engine + miner for the standalone drills: one curated
    pattern, line cache on, worker NOT started (pump() is driven
    explicitly so every step is deterministic)."""
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.models.pattern import (
        Pattern, PatternSet, PatternSetMetadata, PrimaryPattern,
    )
    from log_parser_tpu.runtime import AnalysisEngine

    sets = [
        PatternSet(
            metadata=PatternSetMetadata(library_id="curated", name="curated"),
            patterns=[
                Pattern(
                    id="curated-1",
                    name="curated",
                    severity="HIGH",
                    primary_pattern=PrimaryPattern(
                        regex=curated_regex, confidence=0.8
                    ),
                )
            ],
        )
    ]
    engine = AnalysisEngine(sets, ScoringConfig())
    engine.enable_line_cache(4)
    engine.enable_miner(
        mode=mode, min_support=3, stability=0, autostart=False
    )
    return engine, sets


def _miner_pod(lines: list[str]):
    from log_parser_tpu.models.pod import PodFailureData

    return PodFailureData(pod={"metadata": {"name": "chaos"}}, logs="\n".join(lines))


def scenario_miner_reject_identity():
    """A vet-rejected candidate must leave the serving bank OBJECT-
    identical — not rebuilt-equal, the same object — and the reload epoch
    untouched. The curated pattern's regex is byte-identical to what the
    synthesizer will emit, so admission rejects at the duplicate gate."""
    engine, _ = _miner_engine(
        r"FooBarBazQux\s{1,8}happened\s{1,8}at\s{1,8}\S{1,64}"
    )
    bank_before = engine.bank
    epoch_before = engine.reload_epoch
    engine.analyze(_miner_pod(
        [f"FooBarBazQux happened at t{i}" for i in range(4)]
    ))
    engine.miner.pump()
    stats = engine.miner.stats()
    assert stats["rejected"].get("mined-duplicate") == 1, stats
    assert stats["admitted"] == 0 and stats["errors"] == 0, stats
    assert engine.bank is bank_before, "rejection rebuilt the bank"
    assert engine.reload_epoch == epoch_before, engine.reload_epoch
    engine.miner.stop()


def scenario_miner_reload_race():
    """Mined admission racing a concurrent curated reload: while the
    quiesce gate is held by the curated swap, admission's apply_library
    raises — a retryable mined-swap, never an error or a torn bank. The
    curated reload lands first; the mined candidate re-admits on a later
    pump against the POST-reload library."""
    from log_parser_tpu.runtime.reload import build_candidate

    engine, sets = _miner_engine("OutOfMemoryError")
    engine.analyze(_miner_pod(
        [f"zorblatt collector compacted tier t{i} fine" for i in range(4)]
    ))
    # hold the quiesce gate exactly the way an in-progress curated
    # reload does, then pump: admission must fail CLEANLY into retry
    with engine._quiesce_cv:
        engine._swap_pending = True
    try:
        engine.miner.pump()
    finally:
        with engine._quiesce_cv:
            engine._swap_pending = False
            engine._quiesce_cv.notify_all()
    stats = engine.miner.stats()
    assert stats["retrying"] == 1 and stats["admitted"] == 0, stats
    assert stats["errors"] == 0, stats
    # the curated reload wins the race...
    engine.apply_library(
        build_candidate(sets, engine.config, engine_clock=engine.frequency.clock)
    )
    assert engine.reload_epoch == 1
    # ...and the retry admits against the post-reload library
    engine.miner.pump()
    stats = engine.miner.stats()
    assert stats["admitted"] == 1 and stats["retrying"] == 0, stats
    assert stats["errors"] == 0 and not stats["rejected"], stats
    ids = {p.id for ps in engine.bank.pattern_sets for p in ps.patterns}
    assert "curated-1" in ids and any(i.startswith("mined-") for i in ids), ids
    # the merged library serves: both curated and mined fire
    r = engine.analyze(_miner_pod(
        ["zorblatt collector compacted tier t9 fine", "OutOfMemoryError"]
    ))
    got = {e.matched_pattern.id for e in r.events}
    assert "curated-1" in got and any(i.startswith("mined-") for i in got), got
    engine.miner.stop()


# in-process drills: object identity and deterministic gate-holding need
# the engine in OUR process, not behind HTTP
MINER_STANDALONE = [
    ("miner-reject-identity", scenario_miner_reject_identity),
    ("miner-reload-race", scenario_miner_reload_race),
]


# Fleet group (``--group fleet``; router front-door + signal-driven
# placement — docs/OPS.md "Fleet routing & placement"): a real
# ``--role router`` process proxying to real backend serving processes
# over a consistent-hash ring, with the placement control loop live.


def _fleet(tmp: str, prefix: str, router_flags: list | None = None,
           backend_flags: list | None = None,
           backend_env: dict | None = None,
           router_env: dict | None = None):
    """A router over two backend serving processes sharing one tenant
    library root (migrations need identical pattern config fleet-wide),
    each backend with its own --state-dir. Backends boot and become
    ready BEFORE the router exists, so backend boot latency is never
    counted against --fleet-down-after."""
    root = _make_tenant_root(tmp)
    backends = [
        Server(
            f"{prefix}-backend{i}",
            ["--tenant-root", root,
             "--state-dir", os.path.join(tmp, f"state{i}"),
             *(backend_flags or [])],
            backend_env or {},
        )
        for i in range(2)
    ]
    for b in backends:
        b.wait_ready()
    router = Server(
        f"{prefix}-router",
        ["--role", "router",
         "--backends", ",".join(f"127.0.0.1:{b.port}" for b in backends),
         *(router_flags or [])],
        router_env or {},
    )
    router.wait_ready()
    return router, backends


def _router_metric(url: str, name: str, label: str = "") -> float:
    """Sum of a metric family's samples on the router's /metrics,
    optionally filtered by a label substring."""
    _, text = get_text(url, "/metrics")
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and (not label or label in line):
            try:
                total += float(line.rsplit(None, 1)[1])
            except ValueError:
                pass
    return total


def _poll_until(pred, timeout: float = 30.0, every: float = 0.5):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        last = pred()
        if last:
            return last
        time.sleep(every)
    raise AssertionError(f"condition never held (last: {last!r})")


def scenario_fleet_backend_kill_reroute():
    """SIGKILL one backend of two: the ring evicts it after
    --fleet-down-after failed contacts, every subsequent request —
    including the ones racing the detection window — is served by the
    survivor, and the router's aggregate health stays UP."""
    with tempfile.TemporaryDirectory(prefix="chaos_fleet_") as tmp:
        router, backends = _fleet(
            tmp, "fleet-kill",
            router_flags=["--fleet-down-after", "1", "--fleet-poll-s", "0.5"],
        )
        try:
            # both tenants route through the front-door while the fleet
            # is whole
            for hdr in (None, {"X-Tenant": "acme"}):
                status, _, _ = post(router.url, hdr)
                assert status == 200, status
            assert _router_metric(
                router.url, "logparser_fleet_backends_up"
            ) == 2.0
            backends[0].proc.kill()
            backends[0].proc.wait(10)
            # zero client errors across the detection window: a request
            # that lands on the dead backend retries the next ring owner
            # in-flight
            for i in range(8):
                hdr = {"X-Tenant": "acme"} if i % 2 else None
                status, body, _ = post(router.url, hdr)
                assert status == 200, (i, status, body)
            _poll_until(lambda: _router_metric(
                router.url, "logparser_fleet_backends_up") == 1.0)
            assert _router_metric(
                router.url, "logparser_fleet_reroutes_total", "backend_down"
            ) >= 1.0
            hstatus, health = get(router.url, "/q/health")
            assert hstatus == 200 and health["status"] == "UP", (
                hstatus, health,
            )
            _, fleet = get(router.url, "/fleet/status")
            assert len(fleet["ring"]["backends"]) == 1, fleet["ring"]
            assert fleet["ring"]["remaps"] > 0, fleet["ring"]
            down = fleet["backends"][
                f"http://127.0.0.1:{backends[0].port}"]
            assert not down["up"] and down["lastError"], down
        finally:
            router.stop()
            for b in backends:
                b.stop()


def scenario_fleet_hot_tenant_automove():
    """A tenant burning its rate quota (429 sheds on the backend) is
    live-migrated by the placer: the shed rate is scraped off the
    backend's own /metrics, the move runs the real migrate protocol,
    and the tenant serves from its new owner — clients never see a
    5xx, only 200s and the structured 429s the quota already answers."""
    with tempfile.TemporaryDirectory(prefix="chaos_fleet_") as tmp:
        router, backends = _fleet(
            tmp, "fleet-hot",
            router_flags=["--fleet-poll-s", "0.5",
                          "--fleet-shed-rate", "0.5",
                          "--fleet-down-after", "10"],
            # PAYLOAD is 3 lines; lines/s 2 = a 4-token bucket, so a
            # concurrent burst sheds structured 429s per tenant
            backend_flags=["--tenant-lines-per-s", "2"],
        )
        try:
            hdr = {"X-Tenant": "acme"}
            assert post(router.url, hdr)[0] == 200
            statuses = []
            # sustained sheds across several placer polls
            for _ in range(3):
                burst = Burst(router.url, 6, hdr)
                statuses.extend(s for s, _ in burst.join())
                time.sleep(0.6)
            assert set(statuses) <= {200, 429}, statuses
            assert 429 in statuses, statuses
            _poll_until(lambda: _router_metric(
                router.url, "logparser_fleet_moves_total") >= 1.0)
            assert _router_metric(
                router.url, "logparser_fleet_moves_total", "quota_shed"
            ) >= 1.0
            # the moved tenant serves from its new owner once the token
            # bucket refills; the router already routes there (the
            # override was installed on the migrate ack)
            def served():
                status, _, _ = post(router.url, hdr)
                return status == 200
            _poll_until(served, timeout=15.0)
            _, fleet = get(router.url, "/fleet/status")
            assert fleet["placement"]["movesFailed"] == 0, fleet["placement"]
        finally:
            router.stop()
            for b in backends:
                b.stop()


def scenario_fleet_budget_rebalance():
    """Fleet-arbitrated budgets land on both sides: the router splits
    --fleet-cache-mb / --fleet-tenant-budget-mb from observed traffic
    and pushes POST /admin/budget; each backend's /trace/last shows the
    applied share replacing its boot-time flag value."""
    with tempfile.TemporaryDirectory(prefix="chaos_fleet_") as tmp:
        router, backends = _fleet(
            tmp, "fleet-budget",
            router_flags=["--fleet-poll-s", "0.5",
                          "--fleet-cache-mb", "32",
                          "--fleet-tenant-budget-mb", "48"],
            backend_flags=["--line-cache-mb", "64"],
        )
        try:
            for hdr in (None, {"X-Tenant": "acme"}):
                assert post(router.url, hdr)[0] == 200

            def applied():
                shares = []
                for b in backends:
                    _, trace = get(b.url, "/trace/last")
                    cache_mb = trace.get("lineCache", {}).get("budgetMb")
                    tenant_mb = trace.get("tenants", {}).get("budgetMb")
                    if cache_mb is None or cache_mb == 64.0:
                        return None  # boot-time flag value still in force
                    if not tenant_mb:
                        return None
                    shares.append((cache_mb, tenant_mb))
                return shares

            shares = _poll_until(applied)
            # the shares partition the fleet-wide budgets (floor 8 MiB
            # each plus the traffic-proportional pool)
            assert abs(sum(s[0] for s in shares) - 32.0) < 0.1, shares
            assert abs(sum(s[1] for s in shares) - 48.0) < 0.1, shares
            assert all(s[0] >= 8.0 and s[1] >= 8.0 for s in shares), shares
            _, fleet = get(router.url, "/fleet/status")
            budget = fleet["placement"]["budget"]
            assert len(budget) == 2, budget
            assert _router_metric(
                router.url, "logparser_fleet_budget_mb", "line_cache"
            ) > 0.0
        finally:
            router.stop()
            for b in backends:
                b.stop()


FLEET_STANDALONE = [
    ("fleet-backend-kill-reroute", scenario_fleet_backend_kill_reroute),
    ("fleet-hot-tenant-automove", scenario_fleet_hot_tenant_automove),
    ("fleet-budget-rebalance", scenario_fleet_budget_rebalance),
]


# Pressure group (``--group pressure``; resource-exhaustion ladder —
# docs/OPS.md "Resource exhaustion"): the disk watermark ladder, the
# durability-degrade/re-arm cycle, and retry-budget shedding, all forced
# through the ``disk_enospc`` / ``retry_storm`` fault sites so the
# drills run on any host without filling a real disk.


def scenario_pressure_soft_compaction():
    """Soft disk pressure (a ``watermark:soft`` probe raise): the ladder
    reclaims — a seeded terminal migration journal compacts past its
    decision records — while /q/health answers 200 with a DEGRADED
    pressure check and responses stay 200 WITHOUT the ``durability``
    stamp: soft reclaims space, it never downgrades durability."""
    from log_parser_tpu.runtime.migrate import MIGRATE_DIR, MigrationJournal

    with tempfile.TemporaryDirectory(prefix="chaos_pressure_") as tmp:
        state = os.path.join(tmp, "state")
        # a finished migration's source journal: begin + chatter +
        # cutover + complete. Only [begin, cutover, complete] matter
        # after the terminal record — compaction must reclaim the rest.
        seeded = os.path.join(state, MIGRATE_DIR, "m-old.src.wal")
        jr = MigrationJournal(seeded)
        jr.append("begin", mid="m-old", tenant="ghost",
                  src="local", dst="http://127.0.0.1:1")
        for i in range(16):
            jr.append("copy", chunk=i)
        jr.append("cutover", location="http://127.0.0.1:1", retryAfterS=1)
        jr.append("complete")
        jr.close()
        srv = Server(
            "pressure-soft",
            ["--state-dir", state],
            {"LOG_PARSER_TPU_FAULTS":
                 "disk_enospc_raise@match=watermark:soft"},
        )
        try:
            srv.wait_ready()
            status, body, _ = post(srv.url)
            assert status == 200, (status, body)
            assert "durability" not in body, body
            hstatus, health = get(srv.url, "/q/health")
            assert hstatus == 200, (hstatus, health)
            pres = [c for c in health.get("checks", [])
                    if c.get("name") == "pressure"]
            assert pres and pres[0]["status"] == "DEGRADED", health
            assert pres[0]["data"]["disk"] == "soft", health
            _, trace = get(srv.url, "/trace/last")
            p = trace["pressure"]
            assert p["disk"] == "soft", p
            assert p["compacted"].get("migration", 0) >= 1, p
            kinds = [r.get("k") for r in MigrationJournal.replay(seeded)]
            assert kinds == ["begin", "cutover", "complete"], kinds
            srv.stop(expect_zero=True)
        finally:
            srv.stop()


def scenario_pressure_hard_degrade_rearm():
    """Hard disk pressure forced for a few polls (``watermark:hard``
    raise, @times-bounded): responses stay 200 but carry ``durability:
    degraded`` and the WAL diverts to the in-memory ring; when the
    fault exhausts, the ladder re-arms from a clean snapshot barrier
    and the stamp disappears — its absence is the durability promise."""
    with tempfile.TemporaryDirectory(prefix="chaos_pressure_") as tmp:
        state = os.path.join(tmp, "state")
        srv = Server(
            "pressure-hard",
            ["--state-dir", state],
            # match-specs only consume on their own key, so @times=N is
            # exactly N ladder polls pinned hard (~N seconds at the 1s
            # poll) — sized to outlive the first request's jit warm-up
            {"LOG_PARSER_TPU_FAULTS":
                 "disk_enospc_raise@match=watermark:hard@times=45"},
        )
        try:
            srv.wait_ready()
            status, body, _ = post(srv.url)
            assert status == 200, (status, body)
            assert body.get("durability") == "degraded", body
            hstatus, health = get(srv.url, "/q/health")
            pres = [c for c in health.get("checks", [])
                    if c.get("name") == "pressure"]
            assert hstatus == 200 and pres, (hstatus, health)
            assert pres[0]["data"]["disk"] == "hard", health
            _, trace = get(srv.url, "/trace/last")
            assert trace["journal"]["degraded"] is True, trace["journal"]
            assert trace["journal"]["degradedRecords"] >= 1, trace["journal"]

            def recovered():
                _, t = get(srv.url, "/trace/last")
                return t["pressure"]["disk"] == "ok"
            _poll_until(recovered, timeout=90.0)
            status, body, _ = post(srv.url)
            assert status == 200, (status, body)
            assert "durability" not in body, body
            _, trace = get(srv.url, "/trace/last")
            assert trace["journal"]["degraded"] is False, trace["journal"]
            assert trace["pressure"]["transitions"].get("disk:ok", 0) >= 1, (
                trace["pressure"]
            )
            hstatus, health = get(srv.url, "/q/health")
            assert hstatus == 200 and not [
                c for c in health.get("checks", [])
                if c.get("name") == "pressure"
            ], health
            srv.stop(expect_zero=True)
        finally:
            srv.stop()


def scenario_pressure_retry_storm_shed():
    """A dead backend under an armed ``retry_storm`` fault: the
    router's re-route retries shed a structured 503 ``retry budget
    exhausted`` instead of hammering the fleet, and once the request
    path has evicted the corpse, requests serve 200 again. The control
    fleet — the SAME kill and fault with ``--retry-budget 0`` — retries
    unbounded straight to a 200, which is exactly the storm the budget
    converts into deterministic sheds."""
    from log_parser_tpu.fleet.ring import HashRing

    # the pump poll is parked at 30s so ONLY request-path failures
    # (--fleet-down-after 2) evict the corpse: the shed sequence is
    # then deterministic, not a race against the health loop
    flags = ["--fleet-poll-s", "30", "--fleet-down-after", "2"]
    storm = {"LOG_PARSER_TPU_FAULTS": "retry_storm_raise"}
    hdr = {"X-Tenant": "acme"}

    def kill_owner(router, backends):
        # ports are random per run, so compute acme's ring owner the
        # way the router does and kill exactly that backend
        urls = [f"http://127.0.0.1:{b.port}" for b in backends]
        victim = backends[urls.index(HashRing(urls).owner("acme"))]
        victim.proc.kill()
        victim.proc.wait(10)

    with tempfile.TemporaryDirectory(prefix="chaos_pressure_") as tmp:
        router, backends = _fleet(
            tmp, "pressure-storm", router_flags=flags, router_env=storm,
        )
        try:
            assert post(router.url, hdr)[0] == 200
            kill_owner(router, backends)
            # first post: the attempt on the corpse fails, the re-route
            # wants a retry token, the storm fault says the bucket is
            # dry -> structured shed
            status, body, _ = post(router.url, hdr)
            assert status == 503, (status, body)
            assert body.get("error") == "retry budget exhausted", body
            assert _router_metric(
                router.url, "logparser_pressure_retry_total", "shed"
            ) >= 1.0

            # each shed post still charged the corpse one failure; once
            # it leaves the ring the survivor answers first-attempt (no
            # retry, so the armed storm fault never fires)
            def served():
                status, body, _ = post(router.url, hdr)
                if status == 503:
                    assert body.get("error") == "retry budget exhausted", body
                    return False
                return status == 200
            _poll_until(served, timeout=20.0)
        finally:
            router.stop()
            for b in backends:
                b.stop()

    with tempfile.TemporaryDirectory(prefix="chaos_pressure_") as tmp:
        router, backends = _fleet(
            tmp, "pressure-storm-ctl",
            router_flags=[*flags, "--retry-budget", "0"], router_env=storm,
        )
        try:
            assert post(router.url, hdr)[0] == 200
            kill_owner(router, backends)
            # unbounded control: the same fault is armed but a disabled
            # budget never consults it — the very first post retries
            # through the corpse (evicting it) to the survivor's 200
            status, body, _ = post(router.url, hdr)
            assert status == 200, (status, body)
            assert _router_metric(
                router.url, "logparser_pressure_retry_total", "shed"
            ) == 0.0
        finally:
            router.stop()
            for b in backends:
                b.stop()


PRESSURE_STANDALONE = [
    ("pressure-soft-compaction", scenario_pressure_soft_compaction),
    ("pressure-hard-degrade-rearm", scenario_pressure_hard_degrade_rearm),
    ("pressure-retry-storm-shed", scenario_pressure_retry_storm_shed),
]


SCENARIOS = [
    ("baseline", [], {}, scenario_baseline),
    (
        "device-raise",
        # cache off: identical chaos payloads are full line-cache hits
        # after the first request, which would skip the device site
        ["--line-cache-mb", "0"],
        {
            "LOG_PARSER_TPU_FAULTS": "device_raise:0.5",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_device_raise,
    ),
    (
        "device-wedge",
        ["--device-timeout", "2.0"],
        {
            "LOG_PARSER_TPU_FAULTS": "device_hang:inf@after=1@times=1",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
            "LOG_PARSER_TPU_BREAKER_COOLDOWN_S": "600",
        },
        scenario_device_wedge,
    ),
    (
        "queue-shed",
        ["--max-inflight", "1", "--max-queue", "1"],
        {
            "LOG_PARSER_TPU_FAULTS": "ingest_slow:1.0@after=1",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_queue_shed,
    ),
    (
        "drain",
        ["--drain-s", "20"],
        {
            "LOG_PARSER_TPU_FAULTS": "ingest_slow:2.0@after=1@times=1",
            "LOG_PARSER_TPU_FAULT_SEED": "42",
        },
        scenario_drain,
    ),
]


def _group_registry() -> dict[str, list[str]]:
    """Every scenario name by group — the source of truth ``--list``
    prints and ``--only`` can be checked against."""
    return {
        "base": [s[0] for s in SCENARIOS],
        "batcher": [s[0] for s in BATCHER_SCENARIOS],
        "state": [s[0] for s in STATE_SCENARIOS]
        + [s[0] for s in STATE_STANDALONE],
        "poison": [s[0] for s in POISON_SCENARIOS],
        "linecache": [s[0] for s in LINECACHE_SCENARIOS],
        "kernel": [s[0] for s in KERNEL_SCENARIOS],
        "streaming": [s[0] for s in STREAMING_SCENARIOS],
        "distributed": [s[0] for s in DISTRIBUTED_SCENARIOS],
        "tenant": [s[0] for s in TENANT_STANDALONE],
        "miner": [s[0] for s in MINER_SCENARIOS]
        + [s[0] for s in MINER_STANDALONE],
        "obs": [s[0] for s in OBS_SCENARIOS],
        "spans": [s[0] for s in SPANS_SCENARIOS],
        "migrate": [s[0] for s in MIGRATE_STANDALONE],
        "replica": [s[0] for s in REPLICA_STANDALONE],
        "fleet": [s[0] for s in FLEET_STANDALONE],
        "pressure": [s[0] for s in PRESSURE_STANDALONE],
    }


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="chaos_sweep")
    parser.add_argument("--only", help="run a single scenario by name")
    parser.add_argument(
        "--list", action="store_true",
        help="print every scenario (group + name) and exit",
    )
    parser.add_argument(
        "--json", metavar="PATH",
        help="also write the result table to PATH as a JSON artifact",
    )
    parser.add_argument(
        "--group",
        choices=(
            "base", "batcher", "state", "poison", "linecache", "kernel",
            "streaming", "distributed", "tenant", "miner", "obs", "spans",
            "migrate", "replica", "fleet", "pressure", "all",
        ),
        default="base",
        help="which scenario group to sweep (default: base; the "
        "distributed group needs multi-process CPU collective support)",
    )
    parser.add_argument(
        "--keep-logs", action="store_true",
        help="keep child logs even for passing scenarios",
    )
    args = parser.parse_args(argv)

    if args.list:
        registry = _group_registry()
        width = max(len(g) for g in registry)
        for group, names in registry.items():
            for name in names:
                print(f"{group:<{width}}  {name}")
        return 0

    rows = []
    failed = 0
    single_server = []
    if args.group in ("base", "all"):
        single_server.extend(SCENARIOS)
    if args.group in ("batcher", "all"):
        single_server.extend(BATCHER_SCENARIOS)
    if args.group in ("state", "all"):
        single_server.extend(STATE_SCENARIOS)
    if args.group in ("poison", "all"):
        single_server.extend(POISON_SCENARIOS)
    if args.group in ("linecache", "all"):
        single_server.extend(LINECACHE_SCENARIOS)
    if args.group in ("kernel", "all"):
        single_server.extend(KERNEL_SCENARIOS)
    if args.group in ("streaming", "all"):
        single_server.extend(STREAMING_SCENARIOS)
    if args.group in ("miner", "all"):
        single_server.extend(MINER_SCENARIOS)
    if args.group in ("obs", "all"):
        single_server.extend(OBS_SCENARIOS)
    if args.group in ("spans", "all"):
        single_server.extend(SPANS_SCENARIOS)
    if single_server:
        for name, flags, env, check in single_server:
            if args.only and name != args.only:
                continue
            t0 = time.monotonic()
            srv = Server(name, flags, env)
            try:
                srv.wait_ready()
                check(srv)
                if name != "drain":  # drain stops (and asserts on) itself
                    srv.stop()
                rows.append((name, "PASS", time.monotonic() - t0, ""))
                if not args.keep_logs:
                    os.unlink(srv.log.name)
            except Exception as exc:  # one row per scenario, keep sweeping
                srv.stop()
                failed += 1
                rows.append((name, "FAIL", time.monotonic() - t0,
                             f"{exc} (log: {srv.log.name})"))
    standalone = []
    if args.group in ("state", "all"):
        standalone.extend(STATE_STANDALONE)
    if args.group in ("tenant", "all"):
        standalone.extend(TENANT_STANDALONE)
    if args.group in ("miner", "all"):
        standalone.extend(MINER_STANDALONE)
    if args.group in ("migrate", "all"):
        standalone.extend(MIGRATE_STANDALONE)
    if args.group in ("replica", "all"):
        standalone.extend(REPLICA_STANDALONE)
    if args.group in ("fleet", "all"):
        standalone.extend(FLEET_STANDALONE)
    if args.group in ("pressure", "all"):
        standalone.extend(PRESSURE_STANDALONE)
    for name, check in standalone:
        if args.only and name != args.only:
            continue
        t0 = time.monotonic()
        try:
            check()
            rows.append((name, "PASS", time.monotonic() - t0, ""))
        except Exception as exc:
            failed += 1
            rows.append((name, "FAIL", time.monotonic() - t0, str(exc)))
    if args.group in ("distributed", "all"):
        for name, flags, env, check in DISTRIBUTED_SCENARIOS:
            if args.only and name != args.only:
                continue
            t0 = time.monotonic()
            pair = DistributedPair(name, flags, env)
            try:
                pair.coord.wait_ready(timeout=180)
                check(pair)
                pair.stop()
                rows.append((name, "PASS", time.monotonic() - t0, ""))
                if not args.keep_logs:
                    os.unlink(pair.coord.log.name)
                    os.unlink(pair.follower_log.name)
            except Exception as exc:
                tail = pair.logs_tail()
                pair.stop()
                if _NO_CPU_MULTIPROCESS in tail:
                    rows.append((name, "SKIP", time.monotonic() - t0,
                                 "CPU backend lacks multi-process collectives"))
                else:
                    failed += 1
                    rows.append((name, "FAIL", time.monotonic() - t0,
                                 f"{exc} (logs: {pair.coord.log.name}, "
                                 f"{pair.follower_log.name})"))

    width = max(len(r[0]) for r in rows) if rows else 8
    print(f"\n{'scenario':<{width}}  result  seconds  detail")
    for name, result, secs, detail in rows:
        print(f"{name:<{width}}  {result:<6}  {secs:7.1f}  {detail}")
    passed = sum(1 for r in rows if r[1] == "PASS")
    print(f"\n{passed}/{len(rows)} scenarios passed (seed 42)")
    if args.json:
        artifact = {
            "tool": "chaos_sweep",
            "group": args.group,
            "seed": 42,
            "passed": passed,
            "failed": failed,
            "skipped": sum(1 for r in rows if r[1] == "SKIP"),
            "scenarios": [
                {"name": name, "result": result,
                 "seconds": round(secs, 2), "detail": detail}
                for name, result, secs, detail in rows
            ],
        }
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.json}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
