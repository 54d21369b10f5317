"""CLI entry: ``python -m log_parser_tpu.serve --pattern-dir /shared/patterns``.

Mirrors the reference's boot sequence: load the pattern directory at startup
(PatternService @PostConstruct, PatternService.java:45-69), then serve
``POST /parse`` on :8080 (Dockerfile.native:28). Config comes from a Java
``.properties`` file (``--config``), environment variables (MicroProfile
convention), or flags — flags win.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import os
import sys

from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.patterns import load_pattern_directory
from log_parser_tpu.runtime import AnalysisEngine
from log_parser_tpu.serve.admission import install_drain_handlers
from log_parser_tpu.serve.http import make_server


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="log_parser_tpu.serve")
    parser.add_argument("--pattern-dir", help="pattern YAML directory (pattern.directory)")
    parser.add_argument("--config", help="Java .properties config file")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8080)
    parser.add_argument("--log-level", default="INFO")
    # fleet router front-door (docs/OPS.md "Fleet routing & placement")
    parser.add_argument(
        "--role", default="serve", choices=("serve", "router"),
        help="'serve' boots the engine process (default); 'router' boots "
        "the fleet front-door instead: no engine, no patterns — requests "
        "are proxied to --backends by consistent hashing on the tenant id "
        "(log_parser_tpu/fleet/)",
    )
    parser.add_argument(
        "--backends", default=None, metavar="HOST:PORT,...",
        help="router mode: comma-separated backend serving processes "
        "(HTTP base addresses) forming the consistent-hash ring",
    )
    parser.add_argument(
        "--backends-shim", default=None, metavar="HOST:PORT,...",
        help="router mode: the framed-shim address of each --backends "
        "entry (same order); enables the router's framed front on "
        "--shim-port",
    )
    parser.add_argument(
        "--shim-port", type=int, default=None, metavar="PORT",
        help="router mode: listen port for the framed Envelope front-door "
        "(requires --backends-shim)",
    )
    parser.add_argument(
        "--grpc-port", type=int, default=None, metavar="PORT",
        help="router mode: listen port for the gRPC front-door, proxied "
        "over the framed back-channel (requires --backends-shim; "
        "disabled when grpcio is absent)",
    )
    parser.add_argument(
        "--fleet-vnodes", type=int, default=64,
        help="virtual nodes per backend on the consistent-hash ring "
        "(router mode; default 64)",
    )
    parser.add_argument(
        "--fleet-down-after", type=int, default=2,
        help="consecutive probe/proxy failures before a backend leaves "
        "the ring; it re-joins on the first healthy probe (router mode)",
    )
    parser.add_argument(
        "--fleet-poll-s", type=float, default=2.0, metavar="SECONDS",
        help="placement control-loop poll interval over backend "
        "/q/health + /metrics (router mode; fleet/placement.py)",
    )
    parser.add_argument(
        "--fleet-burn-polls", type=int, default=3,
        help="consecutive polls with SLO burn rate > 1 before the placer "
        "moves the backend's hottest tenant (router mode)",
    )
    parser.add_argument(
        "--fleet-shed-rate", type=float, default=1.0, metavar="PER_S",
        help="per-tenant 429/503 rate that triggers a live move of that "
        "tenant; 0 is never reached in practice (router mode)",
    )
    parser.add_argument(
        "--fleet-thrash-rebuilds", type=int, default=3,
        help="tenant-engine rebuilds within one poll window that count "
        "as residency thrash and trigger a move (router mode)",
    )
    parser.add_argument(
        "--fleet-move-cooldown-s", type=float, default=30.0,
        metavar="SECONDS",
        help="minimum seconds between placer-initiated moves of the SAME "
        "tenant, so a flapping signal cannot ping-pong it (router mode)",
    )
    parser.add_argument(
        "--fleet-cache-mb", type=float, default=0.0, metavar="MB",
        help="fleet-wide line-cache budget arbitrated across backends "
        "from observed traffic, pushed via POST /admin/budget — replaces "
        "per-process --line-cache-mb; 0 disables (router mode)",
    )
    parser.add_argument(
        "--fleet-tenant-budget-mb", type=float, default=0.0, metavar="MB",
        help="fleet-wide tenant-residency budget arbitrated across "
        "backends from observed traffic — replaces per-process "
        "--tenant-budget-mb; 0 disables (router mode)",
    )
    parser.add_argument(
        "--sharded",
        action="store_true",
        help="shard the line batch over every visible device (jax mesh)",
    )
    # multi-process (DCN) scale-out: one mesh spanning processes. Process 0
    # serves HTTP and broadcasts each request; the rest follow
    # (parallel/distributed.py; SURVEY.md §5.8).
    parser.add_argument(
        "--coordinator",
        help="host:port of the jax.distributed coordinator (enables "
        "multi-process mode; implies --sharded)",
    )
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    # distributed resilience (docs/OPS.md "Distributed failure modes")
    parser.add_argument(
        "--broadcast-timeout", type=float, default=None, metavar="SECONDS",
        help="deadline per coordinator→follower dispatch attempt; 0 = "
        "unbounded (LOG_PARSER_TPU_BROADCAST_TIMEOUT_S)",
    )
    parser.add_argument(
        "--broadcast-retries", type=int, default=None,
        help="extra dispatch attempts after a pre-collective timeout "
        "(LOG_PARSER_TPU_BROADCAST_RETRIES)",
    )
    parser.add_argument(
        "--heartbeat-s", type=float, default=None, metavar="SECONDS",
        help="follower heartbeat interval on the coordinator; 0 disables "
        "(LOG_PARSER_TPU_HEARTBEAT_S)",
    )
    parser.add_argument(
        "--dead-after", type=int, default=None,
        help="consecutive dispatch failures before the follower group is "
        "declared dead and serving degrades to local "
        "(LOG_PARSER_TPU_DEAD_AFTER)",
    )
    parser.add_argument(
        "--device-timeout",
        type=float,
        default=None,
        metavar="SECONDS",
        help="watchdog deadline for the device step: a wedged backend "
        "trips the circuit and requests serve from the host path until "
        "it responds (default: off; also LOG_PARSER_TPU_DEVICE_TIMEOUT_S)",
    )
    # overload controls (docs/OPS.md "Overload & degradation") — flags win
    # over the LOG_PARSER_TPU_* env vars they mirror
    parser.add_argument(
        "--max-inflight", type=int, default=None,
        help="bound on concurrently-executing parses; 0 = unbounded "
        "(LOG_PARSER_TPU_MAX_INFLIGHT)",
    )
    parser.add_argument(
        "--max-queue", type=int, default=None,
        help="bound on parses waiting for a slot before the gate sheds "
        "with 429 (LOG_PARSER_TPU_MAX_QUEUE)",
    )
    parser.add_argument(
        "--deadline-ms", type=float, default=None,
        help="default per-request deadline; X-Request-Deadline-Ms "
        "overrides per request (LOG_PARSER_TPU_DEADLINE_MS)",
    )
    parser.add_argument(
        "--drain-s", type=float, default=None,
        help="SIGTERM drain deadline: finish in-flight work up to this "
        "many seconds before exiting (LOG_PARSER_TPU_DRAIN_S)",
    )
    # tenant evacuation (docs/OPS.md "Tenant migration & drain")
    parser.add_argument(
        "--drain-deadline-s", type=float, default=None, metavar="SECONDS",
        help="bound on the drain supervisor's tenant evacuation "
        "(/admin/drain + SIGTERM): past it, remaining tenants close "
        "locally — open stream sessions get an explicit error frame, "
        "never an indefinite hang (default 30; "
        "LOG_PARSER_TPU_DRAIN_DEADLINE_S)",
    )
    parser.add_argument(
        "--drain-target", default=None, metavar="URL",
        help="peer base URL (http://host:port) that drained tenants "
        "migrate to via the crash-safe migration protocol "
        "(runtime/migrate.py); unset = tenants close locally on drain "
        "(LOG_PARSER_TPU_DRAIN_TARGET)",
    )
    parser.add_argument(
        "--drain-on-burn", type=float, default=None, metavar="SECONDS",
        help="poll interval for the health-driven drain trigger: when "
        "/q/health SLO burn goes DEGRADED or the device breaker sticks "
        "open, the supervisor evacuates this process; 0 disables "
        "(default 0; LOG_PARSER_TPU_DRAIN_ON_BURN)",
    )
    # warm-standby replication (docs/OPS.md "Warm-standby replication")
    parser.add_argument(
        "--replica-target", default=None, metavar="URL",
        help="standby base URL (http://host:port) every tenant's "
        "frequency WAL continuously ships to as it is fsynced "
        "(runtime/replicate.py; requires --state-dir; "
        "LOG_PARSER_TPU_REPLICA_TARGET)",
    )
    parser.add_argument(
        "--replica-of", default=None, metavar="URL",
        help="primary base URL this process is the warm standby of: "
        "boot fenced (every client resolve 307s to the primary), "
        "accept /admin/replica/feed, arm the failover supervisor "
        "(requires --state-dir; LOG_PARSER_TPU_REPLICA_OF)",
    )
    parser.add_argument(
        "--failover-after-s", type=float, default=None, metavar="SECONDS",
        help="consecutive seconds the primary's /q/health must fail "
        "before the standby journals PROMOTE(epoch+1) and takes "
        "ownership; 0 = manual POST /admin/promote only (default 0; "
        "LOG_PARSER_TPU_FAILOVER_AFTER_S)",
    )
    # cross-request micro-batching (docs/OPS.md "Micro-batching")
    parser.add_argument(
        "--batching", choices=("on", "off"), default=None,
        help="coalesce concurrent parses into shared device batches "
        "(runtime/batcher.py; single-device engine only; "
        "LOG_PARSER_TPU_BATCHING)",
    )
    parser.add_argument(
        "--batch-wait-ms", type=float, default=None, metavar="MS",
        help="max time a request waits for batchmates before its bucket "
        "flushes (LOG_PARSER_TPU_BATCH_WAIT_MS)",
    )
    parser.add_argument(
        "--batch-max", type=int, default=None,
        help="requests per coalesced device batch; a full bucket flushes "
        "immediately (LOG_PARSER_TPU_BATCH_MAX)",
    )
    # exact-match line cache (docs/OPS.md "Line cache (routing tier)")
    parser.add_argument(
        "--line-cache-mb", type=float, default=None, metavar="MB",
        help="resident-byte budget of the exact-match line cache: repeat "
        "lines skip the match cube, novel lines run as a compacted "
        "residual batch (runtime/linecache.py; single-device engine "
        "only; 0 disables; default 64; LOG_PARSER_TPU_LINE_CACHE_MB)",
    )
    # template miner (docs/OPS.md "Template miner")
    parser.add_argument(
        "--miner", choices=("on", "off"), default=None,
        help="mine templates from the line-cache miss stream "
        "(log_parser_tpu/mining/; requires --line-cache-mb > 0; "
        "single-device engine only; default off; LOG_PARSER_TPU_MINER)",
    )
    parser.add_argument(
        "--miner-sample", type=float, default=None, metavar="RATE",
        help="fraction of unique cache-miss lines offered to the miner "
        "tap; deterministic stride sampling, never blocks the hot path "
        "(default 1.0; LOG_PARSER_TPU_MINER_SAMPLE)",
    )
    parser.add_argument(
        "--miner-min-support", type=int, default=None,
        help="miss lines a template cluster must absorb before it is "
        "synthesized into a candidate (default 8; "
        "LOG_PARSER_TPU_MINER_MIN_SUPPORT)",
    )
    parser.add_argument(
        "--mined-patterns", default=None, choices=("off", "review", "auto"),
        help="what happens to lint-clean mined candidates: 'review' parks "
        "them for GET/POST /patterns/mined, 'auto' admits through canary "
        "+ quiesced swap with shadow verification forced on, 'off' "
        "clusters without synthesizing; default review "
        "(LOG_PARSER_TPU_MINED_PATTERNS)",
    )
    # streaming follow-mode (docs/OPS.md "Streaming follow-mode")
    parser.add_argument(
        "--stream-emit-threshold", type=float, default=None, metavar="SCORE",
        help="minimum provisional score before a streaming session emits "
        "an event frame early (monotone-refinement contract: emitted "
        "scores may firm up, retractions are explicit 'revised' frames; "
        "default 0 emits everything; "
        "LOG_PARSER_TPU_STREAM_EMIT_THRESHOLD)",
    )
    parser.add_argument(
        "--stream-ttl-s", type=float, default=None, metavar="SECONDS",
        help="idle streaming sessions are reaped (and their admission "
        "slot released) after this long without a chunk; 0 disables "
        "the reaper (default 300; LOG_PARSER_TPU_STREAM_TTL_S)",
    )
    # poison-request quarantine + online shadow verification
    # (docs/OPS.md "Poison-request triage" / "Shadow divergence")
    parser.add_argument(
        "--quarantine-strikes", type=int, default=None,
        help="organic device-failure strikes before a request fingerprint "
        "is quarantined to the golden host path "
        "(LOG_PARSER_TPU_QUARANTINE_STRIKES)",
    )
    parser.add_argument(
        "--quarantine-ttl-s", type=float, default=None, metavar="SECONDS",
        help="how long a quarantined fingerprint stays off the device "
        "step before re-admission (LOG_PARSER_TPU_QUARANTINE_TTL_S)",
    )
    parser.add_argument(
        "--shadow-rate", type=float, default=None, metavar="RATE",
        help="fraction of served requests re-run on the golden host path "
        "off the hot path and compared at 1e-9; divergence trips a "
        "per-pattern breaker (0 disables; LOG_PARSER_TPU_SHADOW_RATE)",
    )
    # observability plane (docs/OPS.md "Observability")
    parser.add_argument(
        "--trace-ring", type=int, default=None, metavar="N",
        help="capacity of the bounded request-trace ring behind "
        "GET /trace/recent (default 256; LOG_PARSER_TPU_TRACE_RING)",
    )
    parser.add_argument(
        "--trace-slow-ms", type=float, default=None, metavar="MS",
        help="requests at or above this total latency are also captured "
        "in the slow-request ring (default 500; "
        "LOG_PARSER_TPU_TRACE_SLOW_MS)",
    )
    parser.add_argument(
        "--trace-sample", type=float, default=None, metavar="FRACTION",
        help="head-sampling rate for the causal span store behind "
        "GET /trace/spans: deterministic on the trace id; slow requests "
        "(--trace-slow-ms) and flush/session/tenancy spans are always "
        "kept (default 1.0; LOG_PARSER_TPU_TRACE_SAMPLE)",
    )
    parser.add_argument(
        "--trace-spans", type=int, default=None, metavar="N",
        help="capacity of the bounded causal span store "
        "(default 256; LOG_PARSER_TPU_TRACE_SPANS)",
    )
    parser.add_argument(
        "--slo-p99-ms", type=float, default=None, metavar="MS",
        help="latency objective: p99 of served requests should stay "
        "under this; burn-rate over the multi-window accounting flips "
        "/q/health DEGRADED (0 disables; LOG_PARSER_TPU_SLO_P99_MS)",
    )
    parser.add_argument(
        "--slo-availability", type=float, default=None, metavar="FRACTION",
        help="availability objective, e.g. 0.999: non-5xx fraction of "
        "requests; burn-rate over budget flips /q/health DEGRADED "
        "(0 disables; LOG_PARSER_TPU_SLO_AVAILABILITY)",
    )
    parser.add_argument(
        "--faults", default=None, metavar="SPEC",
        help="fault-injection DSL, e.g. 'device_hang:2@after=3' "
        "(LOG_PARSER_TPU_FAULTS; see runtime/faults.py)",
    )
    parser.add_argument(
        "--fault-seed", type=int, default=None,
        help="PRNG seed for probabilistic fault specs "
        "(LOG_PARSER_TPU_FAULT_SEED)",
    )
    # durable state + hot reload (docs/OPS.md "State durability & recovery")
    parser.add_argument(
        "--state-dir", default=None, metavar="DIR",
        help="directory for the frequency WAL + snapshots; enables crash "
        "recovery across restarts (LOG_PARSER_TPU_STATE_DIR)",
    )
    parser.add_argument(
        "--journal-fsync-ms", type=float, default=None, metavar="MS",
        help="group-fsync interval for the frequency journal "
        "(LOG_PARSER_TPU_JOURNAL_FSYNC_MS)",
    )
    parser.add_argument(
        "--snapshot-every", type=int, default=None,
        help="journal records between background snapshots; a snapshot "
        "truncates the WAL (LOG_PARSER_TPU_SNAPSHOT_EVERY)",
    )
    # resource-pressure plane (docs/OPS.md "Resource exhaustion")
    parser.add_argument(
        "--disk-soft-mb", type=float, default=None, metavar="MB",
        help="free-byte soft watermark over --state-dir: below it every "
        "journal snapshots+truncates and the migration/epoch journals "
        "compact (runtime/pressure.py; 0 disables; "
        "LOG_PARSER_TPU_DISK_SOFT_MB)",
    )
    parser.add_argument(
        "--disk-hard-mb", type=float, default=None, metavar="MB",
        help="free-byte hard watermark: below it journals degrade to a "
        "bounded in-memory ring and responses carry 'durability: "
        "degraded' — the serving path keeps answering 200s (0 disables; "
        "LOG_PARSER_TPU_DISK_HARD_MB)",
    )
    parser.add_argument(
        "--mem-soft-mb", type=float, default=None, metavar="MB",
        help="RSS soft watermark: over it the memory levers apply one "
        "per poll in severity order (line-cache shrink, "
        "tenant eviction, span staging trim, miner tap close), released "
        "in reverse with hysteresis (0 disables; "
        "LOG_PARSER_TPU_MEM_SOFT_MB)",
    )
    parser.add_argument(
        "--retry-budget", type=float, default=None, metavar="RATIO",
        help="retry-budget ratio shared per destination: sustained "
        "retries (shim reconnects, router re-routes, replica sender "
        "backoff) are capped at this fraction of recent first attempts; "
        "exhausted budgets shed 'retry budget exhausted'; 0 disables "
        "(default 0.1; LOG_PARSER_TPU_RETRY_BUDGET)",
    )
    parser.add_argument(
        "--watch-patterns", type=float, default=None, metavar="SECONDS",
        help="poll the pattern directory at this interval and hot-reload "
        "on change (canary-gated, runtime/reload.py); 0 disables "
        "(LOG_PARSER_TPU_WATCH_PATTERNS)",
    )
    parser.add_argument(
        "--lint-patterns", default=None, choices=("off", "warn", "block"),
        help="static-analysis lint stage of the reload ladder "
        "(log_parser_tpu/analysis/): 'warn' records findings on "
        "/trace/last, 'block' rejects a reload with gating findings as "
        "a structured 409; default warn (LOG_PARSER_TPU_LINT_PATTERNS)",
    )
    parser.add_argument(
        "--pallas-dfa", default=None, choices=("on", "off"),
        help="route the union multi-DFA tier through the Pallas scan "
        "kernel (ops/matchdfa_pallas.py); bit-identical to the XLA scan, "
        "falls back per batch on admission or fault; default off "
        "(LOG_PARSER_TPU_PALLAS_DFA)",
    )
    # multi-tenant serving (docs/OPS.md "Multi-tenant serving")
    parser.add_argument(
        "--tenant-root", default=None, metavar="DIR",
        help="root of per-tenant pattern libraries: DIR/<tenant>/ holds "
        "tenant <tenant>'s YAML sets, built lazily on first X-Tenant "
        "request (runtime/tenancy.py; single-device engine only; "
        "LOG_PARSER_TPU_TENANT_ROOT)",
    )
    parser.add_argument(
        "--tenant-budget-mb", type=float, default=None, metavar="MB",
        help="resident byte budget across non-default tenant banks; over "
        "budget the least-recently-used idle tenant is evicted (its "
        "journal snapshots, its next request rebuilds warm from the "
        "library snapshot cache); 0 = unbounded "
        "(LOG_PARSER_TPU_TENANT_BUDGET_MB)",
    )
    parser.add_argument(
        "--tenant-max-inflight", type=int, default=None,
        help="per-tenant cap on concurrently-executing parses inside the "
        "shared gate; 0 = unbounded (LOG_PARSER_TPU_TENANT_MAX_INFLIGHT)",
    )
    parser.add_argument(
        "--tenant-max-queued", type=int, default=None,
        help="per-tenant share of the shared wait queue; 0 = unbounded "
        "(LOG_PARSER_TPU_TENANT_MAX_QUEUED)",
    )
    parser.add_argument(
        "--tenant-lines-per-s", type=float, default=None,
        help="per-tenant sustained log-line rate (token bucket, 2s "
        "burst); a request over budget sheds 429 'tenant rate' with "
        "Retry-After; 0 = unbounded (LOG_PARSER_TPU_TENANT_LINES_PER_S)",
    )
    args = parser.parse_args(argv)
    if args.device_timeout is not None:
        os.environ["LOG_PARSER_TPU_DEVICE_TIMEOUT_S"] = str(args.device_timeout)
    if args.pallas_dfa is not None:
        os.environ["LOG_PARSER_TPU_PALLAS_DFA"] = (
            "1" if args.pallas_dfa == "on" else "0"
        )
    for flag, env_key in (
        (args.max_inflight, "LOG_PARSER_TPU_MAX_INFLIGHT"),
        (args.max_queue, "LOG_PARSER_TPU_MAX_QUEUE"),
        (args.deadline_ms, "LOG_PARSER_TPU_DEADLINE_MS"),
        (args.drain_s, "LOG_PARSER_TPU_DRAIN_S"),
        (args.batching, "LOG_PARSER_TPU_BATCHING"),
        (args.batch_wait_ms, "LOG_PARSER_TPU_BATCH_WAIT_MS"),
        (args.batch_max, "LOG_PARSER_TPU_BATCH_MAX"),
        (args.line_cache_mb, "LOG_PARSER_TPU_LINE_CACHE_MB"),
        (args.miner, "LOG_PARSER_TPU_MINER"),
        (args.miner_sample, "LOG_PARSER_TPU_MINER_SAMPLE"),
        (args.miner_min_support, "LOG_PARSER_TPU_MINER_MIN_SUPPORT"),
        (args.mined_patterns, "LOG_PARSER_TPU_MINED_PATTERNS"),
        (args.stream_emit_threshold, "LOG_PARSER_TPU_STREAM_EMIT_THRESHOLD"),
        (args.stream_ttl_s, "LOG_PARSER_TPU_STREAM_TTL_S"),
        (args.quarantine_strikes, "LOG_PARSER_TPU_QUARANTINE_STRIKES"),
        (args.quarantine_ttl_s, "LOG_PARSER_TPU_QUARANTINE_TTL_S"),
        (args.shadow_rate, "LOG_PARSER_TPU_SHADOW_RATE"),
        (args.trace_ring, "LOG_PARSER_TPU_TRACE_RING"),
        (args.trace_slow_ms, "LOG_PARSER_TPU_TRACE_SLOW_MS"),
        (args.trace_sample, "LOG_PARSER_TPU_TRACE_SAMPLE"),
        (args.trace_spans, "LOG_PARSER_TPU_TRACE_SPANS"),
        (args.slo_p99_ms, "LOG_PARSER_TPU_SLO_P99_MS"),
        (args.slo_availability, "LOG_PARSER_TPU_SLO_AVAILABILITY"),
        (args.faults, "LOG_PARSER_TPU_FAULTS"),
        (args.fault_seed, "LOG_PARSER_TPU_FAULT_SEED"),
        (args.broadcast_timeout, "LOG_PARSER_TPU_BROADCAST_TIMEOUT_S"),
        (args.broadcast_retries, "LOG_PARSER_TPU_BROADCAST_RETRIES"),
        (args.heartbeat_s, "LOG_PARSER_TPU_HEARTBEAT_S"),
        (args.dead_after, "LOG_PARSER_TPU_DEAD_AFTER"),
        (args.state_dir, "LOG_PARSER_TPU_STATE_DIR"),
        (args.journal_fsync_ms, "LOG_PARSER_TPU_JOURNAL_FSYNC_MS"),
        (args.snapshot_every, "LOG_PARSER_TPU_SNAPSHOT_EVERY"),
        (args.disk_soft_mb, "LOG_PARSER_TPU_DISK_SOFT_MB"),
        (args.disk_hard_mb, "LOG_PARSER_TPU_DISK_HARD_MB"),
        (args.mem_soft_mb, "LOG_PARSER_TPU_MEM_SOFT_MB"),
        (args.retry_budget, "LOG_PARSER_TPU_RETRY_BUDGET"),
        (args.watch_patterns, "LOG_PARSER_TPU_WATCH_PATTERNS"),
        (args.lint_patterns, "LOG_PARSER_TPU_LINT_PATTERNS"),
        (args.tenant_root, "LOG_PARSER_TPU_TENANT_ROOT"),
        (args.tenant_budget_mb, "LOG_PARSER_TPU_TENANT_BUDGET_MB"),
        (args.tenant_max_inflight, "LOG_PARSER_TPU_TENANT_MAX_INFLIGHT"),
        (args.tenant_max_queued, "LOG_PARSER_TPU_TENANT_MAX_QUEUED"),
        (args.tenant_lines_per_s, "LOG_PARSER_TPU_TENANT_LINES_PER_S"),
        (args.drain_deadline_s, "LOG_PARSER_TPU_DRAIN_DEADLINE_S"),
        (args.drain_target, "LOG_PARSER_TPU_DRAIN_TARGET"),
        (args.drain_on_burn, "LOG_PARSER_TPU_DRAIN_ON_BURN"),
        (args.replica_target, "LOG_PARSER_TPU_REPLICA_TARGET"),
        (args.replica_of, "LOG_PARSER_TPU_REPLICA_OF"),
        (args.failover_after_s, "LOG_PARSER_TPU_FAILOVER_AFTER_S"),
    ):
        if flag is not None:
            os.environ[env_key] = str(flag)

    logging.basicConfig(
        level=args.log_level.upper(),
        format="%(asctime)s %(levelname)s [%(name)s] %(message)s",
    )
    log = logging.getLogger("log_parser_tpu.serve")

    if args.role == "router":
        # the router holds no engine: no pattern directory, no jax —
        # branch before any of the engine boot requirements below
        return _run_router(args, log)

    config = (
        ScoringConfig.from_properties_file(args.config)
        if args.config
        else ScoringConfig.from_env()
    )
    if args.pattern_dir:
        config = dataclasses.replace(config, pattern_directory=args.pattern_dir)
    if not config.pattern_directory:
        log.error("pattern.directory is required (--pattern-dir / config / env)")
        return 2

    if args.coordinator:
        if args.num_processes is None or args.process_id is None:
            log.error("--coordinator requires --num-processes and --process-id")
            return 2
        from log_parser_tpu.parallel.distributed import init_distributed

        init_distributed(args.coordinator, args.num_processes, args.process_id)

    pattern_sets = load_pattern_directory(config.pattern_directory)
    if args.coordinator:
        from log_parser_tpu.parallel import make_mesh
        from log_parser_tpu.parallel.distributed import DistributedShardedEngine

        mesh = make_mesh()
        engine = DistributedShardedEngine(pattern_sets, config, mesh=mesh)
        log.info(
            "Multi-process mesh: %d devices across %d processes",
            mesh.devices.size,
            args.num_processes,
        )
    elif args.sharded:
        from log_parser_tpu.parallel import ShardedEngine, make_mesh

        mesh = make_mesh()
        engine = ShardedEngine(pattern_sets, config, mesh=mesh)
        log.info("Sharding line batches over %d devices", mesh.devices.size)
    else:
        engine = AnalysisEngine(pattern_sets, config)
    if engine.skipped_patterns:
        for pid, reason in engine.skipped_patterns:
            log.warning("pattern %r disabled: %s", pid, reason)
    log.info(
        "Loaded %d pattern sets (%d patterns, %d matcher columns; %d on-device DFAs)",
        len(pattern_sets),
        engine.bank.n_patterns,
        engine.bank.n_columns,
        sum(1 for c in engine.bank.columns if c.dfa is not None),
    )

    if os.environ.get("LOG_PARSER_TPU_BATCHING", "off").strip().lower() == "on":
        if args.coordinator or args.sharded:
            # the vmapped batch program has no shard_map counterpart yet —
            # the request axis and the line/pattern mesh axes would need a
            # combined layout (ROADMAP)
            log.warning(
                "--batching is only supported on the single-device "
                "engine; serving unbatched"
            )
        else:
            wait_ms = float(os.environ.get("LOG_PARSER_TPU_BATCH_WAIT_MS", "2"))
            batch_max = int(os.environ.get("LOG_PARSER_TPU_BATCH_MAX", "8"))
            engine.enable_batching(wait_ms=wait_ms, batch_max=batch_max)
            log.info(
                "Micro-batching on: wait %.1f ms, batch max %d",
                wait_ms,
                batch_max,
            )

    line_cache_mb = float(
        os.environ.get("LOG_PARSER_TPU_LINE_CACHE_MB", "64") or 0
    )
    if line_cache_mb > 0:
        if args.coordinator or args.sharded:
            # the residual program is the full-bank single-device cube;
            # sharded engines split patterns/lines across devices and
            # keep the uncached path (same gate as --batching)
            log.warning(
                "--line-cache-mb is only supported on the single-device "
                "engine; serving uncached"
            )
        else:
            engine.enable_line_cache(line_cache_mb)
            log.info("Line cache on: %.0f MB budget", line_cache_mb)

    if args.coordinator and args.process_id != 0:
        # followers own no network surface: they replay the coordinator's
        # broadcast requests so every process enters each SPMD dispatch.
        # SIGTERM/SIGINT must NOT kill a follower mid-collective — orderly
        # exit is the coordinator's shutdown sentinel, which arrives after
        # the coordinator finishes draining. A second signal forces out.
        import signal

        signals_seen = {"n": 0}

        def _follower_signal(signum, frame):
            signals_seen["n"] += 1
            if signals_seen["n"] > 1:
                log.warning(
                    "Follower %d: second signal, exiting immediately",
                    args.process_id,
                )
                raise SystemExit(1)
            log.info(
                "Follower %d: signal %d ignored — waiting for the "
                "coordinator's drain sentinel (signal again to force exit)",
                args.process_id,
                signum,
            )

        signal.signal(signal.SIGTERM, _follower_signal)
        signal.signal(signal.SIGINT, _follower_signal)
        log.info("Follower %d ready", args.process_id)
        engine.follower_loop()
        return 0

    # resource-pressure plane: one controller per process, installed
    # BEFORE the journal opens so the very first append is already
    # guarded; journals/levers/compactors attach below as their
    # subsystems come up (runtime/pressure.py, docs/OPS.md "Resource
    # exhaustion")
    from log_parser_tpu.runtime import pressure

    pressure_ctl = pressure.PressureController(
        os.environ.get("LOG_PARSER_TPU_STATE_DIR") or None,
        disk_soft_mb=float(
            os.environ.get("LOG_PARSER_TPU_DISK_SOFT_MB", "0") or 0
        ),
        disk_hard_mb=float(
            os.environ.get("LOG_PARSER_TPU_DISK_HARD_MB", "0") or 0
        ),
        mem_soft_mb=float(
            os.environ.get("LOG_PARSER_TPU_MEM_SOFT_MB", "0") or 0
        ),
        retry_ratio=float(
            os.environ.get("LOG_PARSER_TPU_RETRY_BUDGET", "0.1") or 0
        ),
    )
    pressure.install(pressure_ctl)

    # durable frequency state: recover + journal under --state-dir.
    # Followers never reach this point (follower_loop above), so in
    # distributed mode only the coordinator journals — its tracker is the
    # canonical one; followers converge from the broadcast replay.
    journal = None
    state_dir = os.environ.get("LOG_PARSER_TPU_STATE_DIR")
    if state_dir:
        journal = engine.attach_journal(
            state_dir,
            fsync_ms=float(
                os.environ.get("LOG_PARSER_TPU_JOURNAL_FSYNC_MS", "50")
            ),
            snapshot_every=int(
                os.environ.get("LOG_PARSER_TPU_SNAPSHOT_EVERY", "512")
            ),
        )
        log.info(
            "Frequency journal at %s: epoch %d, %d record(s) replayed%s",
            state_dir,
            journal.epoch,
            journal.replayed,
            ", torn tail quarantined" if journal.torn_tails else "",
        )
        pressure_ctl.register_journal(journal)
        # on-demand device profiling (POST /debug/profile) captures into a
        # state-dir subdirectory; without --state-dir the route answers 503
        engine.obs.profiler.configure(os.path.join(state_dir, "profiles"))
        # shutdown writes the span store as OTLP/JSON here, so the last
        # window of causal trees survives the process
        engine.obs.span_dump_path = os.path.join(state_dir, "spans.otlp.json")

    # template miner: background consumer of the line-cache miss stream
    # (log_parser_tpu/mining/); per-tenant miners are wired below in
    # tenant_engine_setup with the SAME env-carried knobs
    miner_on = (
        os.environ.get("LOG_PARSER_TPU_MINER", "off").strip().lower() == "on"
    )
    miner_sample = float(os.environ.get("LOG_PARSER_TPU_MINER_SAMPLE", "1.0"))
    miner_support = int(
        os.environ.get("LOG_PARSER_TPU_MINER_MIN_SUPPORT", "8")
    )
    miner_mode = (
        os.environ.get("LOG_PARSER_TPU_MINED_PATTERNS", "review")
        .strip()
        .lower()
    )
    if miner_on:
        if args.coordinator or args.sharded:
            log.warning(
                "--miner rides the line cache and is only supported on "
                "the single-device engine; mining disabled"
            )
            miner_on = False
        elif engine.line_cache is None:
            log.warning(
                "--miner requires --line-cache-mb > 0 (the miss stream "
                "IS the cache miss stream); mining disabled"
            )
            miner_on = False
        else:
            engine.enable_miner(
                mode=miner_mode,
                sample=miner_sample,
                min_support=miner_support,
                state_dir=state_dir,
            )
            log.info(
                "Template miner on: mode %s, sample %.3g, min support %d",
                miner_mode,
                miner_sample,
                miner_support,
            )
            pressure_ctl.register_miner(engine.miner)

    # tenant registry: X-Tenant (HTTP) / x-tenant (gRPC) / method@tenant
    # (framed shim) resolve through one registry; each non-default tenant
    # gets a dedicated engine mirroring this one's serving features, all
    # admitting through the ONE shared gate
    from log_parser_tpu.runtime.tenancy import TenantQuota, TenantRegistry
    from log_parser_tpu.serve.admission import shared_gate

    tenant_root = os.environ.get("LOG_PARSER_TPU_TENANT_ROOT") or None
    if tenant_root and (args.coordinator or args.sharded):
        # tenant engines are single-device AnalysisEngines; placing tenant
        # banks across a mesh is parallel/pattern_sharded.py's
        # tenant-placement mode, not the serve path
        log.warning(
            "--tenant-root is only supported on the single-device engine; "
            "serving single-tenant"
        )
        tenant_root = None

    # filled after the replicator is built below; tenant engines that come
    # up later (lazy first-touch builds) attach their WAL senders here
    replication_holder: dict = {"rep": None}

    def tenant_engine_setup(eng, tenant_id: str) -> None:
        # mirror the default engine's serving features; env carries the
        # flag values (the flag→env loop above ran before boot)
        if os.environ.get(
            "LOG_PARSER_TPU_BATCHING", "off"
        ).strip().lower() == "on":
            eng.enable_batching(
                wait_ms=float(
                    os.environ.get("LOG_PARSER_TPU_BATCH_WAIT_MS", "2")
                ),
                batch_max=int(os.environ.get("LOG_PARSER_TPU_BATCH_MAX", "8")),
            )
        mb = float(os.environ.get("LOG_PARSER_TPU_LINE_CACHE_MB", "64") or 0)
        if mb > 0:
            eng.enable_line_cache(mb)
            if miner_on:
                # per-tenant miner: own tap/clusterer/pending store, state
                # namespaced beside the tenant WAL (tenants/<id>/mined/)
                eng.enable_miner(
                    mode=miner_mode,
                    sample=miner_sample,
                    min_support=miner_support,
                    state_dir=(
                        os.path.join(state_dir, "tenants", tenant_id)
                        if state_dir
                        else None
                    ),
                )
        if state_dir:
            # namespaced WAL/snapshot dir: tenants/<id> under the default
            # tenant's state dir, so recovery is per-tenant and a tenant
            # eviction's final snapshot lands where its rebuild looks
            tenant_journal = eng.attach_journal(
                os.path.join(state_dir, "tenants", tenant_id),
                fsync_ms=float(
                    os.environ.get("LOG_PARSER_TPU_JOURNAL_FSYNC_MS", "50")
                ),
                snapshot_every=int(
                    os.environ.get("LOG_PARSER_TPU_SNAPSHOT_EVERY", "512")
                ),
            )
            if tenant_journal is not None:
                # rides the same ladder as the default WAL: soft
                # snapshots it, hard degrades it to its ring
                pressure_ctl.register_journal(tenant_journal)
            rep = replication_holder["rep"]
            if rep is not None:
                # primary side: this tenant's WAL starts shipping to the
                # standby as soon as the engine is up (no-op on standbys)
                rep.attach_sender(tenant_id, eng)

    t_inflight = int(os.environ.get("LOG_PARSER_TPU_TENANT_MAX_INFLIGHT", "0") or 0)
    t_queued = int(os.environ.get("LOG_PARSER_TPU_TENANT_MAX_QUEUED", "0") or 0)
    t_lps = float(os.environ.get("LOG_PARSER_TPU_TENANT_LINES_PER_S", "0") or 0)
    tenants = TenantRegistry(
        engine,
        root=tenant_root,
        budget_mb=float(
            os.environ.get("LOG_PARSER_TPU_TENANT_BUDGET_MB", "0") or 0
        ),
        gate=shared_gate(engine),
        engine_setup=tenant_engine_setup,
        quota_factory=lambda tid: TenantQuota(t_inflight, t_queued, t_lps),
        lint_mode=os.environ.get("LOG_PARSER_TPU_LINT_PATTERNS", "warn"),
    )
    if tenant_root:
        log.info(
            "Multi-tenant serving: root %s, bank budget %s, quota "
            "inflight=%d queued=%d lines/s=%.0f",
            tenant_root,
            "unbounded" if tenants.budget_bytes <= 0
            else "%.0f MB" % (tenants.budget_bytes / 2**20),
            t_inflight, t_queued, t_lps,
        )

    try:
        server = make_server(engine, args.host, args.port, tenants=tenants)
    except OSError:
        # followers are already blocked waiting for a broadcast; a
        # coordinator that dies without the shutdown sentinel would hang
        # the whole group
        if args.coordinator:
            engine.shutdown_followers()
        raise
    # SIGTERM/SIGINT drain instead of killing in-flight work: readiness
    # flips to 503, the gate refuses new parses, in-flight ones finish (up
    # to --drain-s), then serve_forever returns and the normal shutdown
    # sequence below runs — including the follower sentinel in distributed
    # mode, which therefore always lands AFTER the drain, never
    # mid-broadcast (the analyze lock covers the straggler case).
    # streaming follow-mode sessions: same single-device gate as
    # --batching / --line-cache-mb (the session residual program is the
    # full-bank cube). The manager is created eagerly so the TTL reaper
    # runs from boot, not from the first streaming request.
    if args.coordinator or args.sharded:
        server.stream_enabled = False
        log.warning(
            "streaming sessions are only supported on the single-device "
            "engine; POST /parse/stream disabled"
        )
    else:
        mgr = server.get_stream_manager()
        log.info(
            "Streaming on: emit threshold %.3g, session TTL %.0fs",
            mgr.emit_threshold,
            mgr.ttl_s,
        )
    # crash-safe tenant migration + health-driven drain (runtime/migrate.py,
    # docs/OPS.md "Tenant migration & drain"). The Migrator needs --state-dir
    # for its per-migration journals; the DrainSupervisor is wired
    # unconditionally so /admin/drain and SIGTERM finalize EVERY resident
    # tenant (fold WALs, flush batchers, dump spans) even on stateless nodes.
    from log_parser_tpu.runtime.migrate import (
        DrainSupervisor,
        HttpTarget,
        Migrator,
    )

    drain_deadline = float(
        os.environ.get("LOG_PARSER_TPU_DRAIN_DEADLINE_S", "30") or 30
    )
    drain_target_url = (
        os.environ.get("LOG_PARSER_TPU_DRAIN_TARGET", "").strip() or None
    )
    migrator = None
    if state_dir:
        migrator = Migrator(
            tenants,
            state_root=state_dir,
            node_url=f"http://{args.host}:{args.port}",
        )
        server.migrator = migrator
        # boot-time recovery: exactly-one-owner after any crash — re-install
        # forwards for cut-over migrations, resume the ones whose target we
        # still know, discard half-staged imports
        recovered = migrator.recover(
            {drain_target_url: HttpTarget(drain_target_url)}
            if drain_target_url
            else None
        )
        if any(v for v in recovered.values()):
            log.info(
                "Migration recovery: %d forward(s) re-installed, "
                "%d resumed, %d staged import(s) discarded, %d pending",
                len(recovered["forwards"]),
                len(recovered["resumed"]),
                len(recovered["discarded"]),
                len(recovered["pending"]),
            )
        # bounded growth: terminal migration journals compact at boot
        # and on every entry into soft disk pressure
        pressure_ctl.register_compactor("migration", migrator.compact)
    drain_supervisor = DrainSupervisor(
        tenants,
        migrator,
        gate=server.admission,
        target=(
            HttpTarget(drain_target_url, timeout_s=max(5.0, drain_deadline))
            if drain_target_url
            else None
        ),
        deadline_s=drain_deadline,
        span_dump_path=engine.obs.span_dump_path,
    )
    server.drain_supervisor = drain_supervisor
    drain_on_burn = float(
        os.environ.get("LOG_PARSER_TPU_DRAIN_ON_BURN", "0") or 0
    )
    if drain_on_burn > 0:

        def _evacuation_check() -> str | None:
            slo = engine.obs.slo.health()
            if slo is not None and slo.get("status") != "UP":
                return "slo-burn"
            if engine.watchdog.circuit_open:
                return "device-breaker"
            return None

        drain_supervisor.watch_health(_evacuation_check, poll_s=drain_on_burn)
        log.info(
            "Health-driven drain armed: poll %.1fs, target %s",
            drain_on_burn,
            drain_target_url or "<close locally>",
        )
    # warm-standby replication + fenced failover (runtime/replicate.py,
    # docs/OPS.md "Warm-standby replication"). A primary (--replica-target)
    # ships every tenant WAL to the standby; a standby (--replica-of) boots
    # fenced, applies feeds, and promotes on sustained primary death.
    replica_target_url = (
        os.environ.get("LOG_PARSER_TPU_REPLICA_TARGET", "").strip() or None
    )
    replica_of_url = (
        os.environ.get("LOG_PARSER_TPU_REPLICA_OF", "").strip() or None
    )
    failover_after = float(
        os.environ.get("LOG_PARSER_TPU_FAILOVER_AFTER_S", "0") or 0
    )
    if (replica_target_url or replica_of_url) and not state_dir:
        log.warning(
            "replication needs --state-dir for the WAL + epoch journal; "
            "--replica-target/--replica-of ignored"
        )
    elif replica_target_url or replica_of_url:
        from log_parser_tpu.runtime.replicate import (
            HttpReplicaTarget,
            Replicator,
        )
        from log_parser_tpu.runtime.tenancy import DEFAULT_TENANT

        replicator = Replicator(
            tenants,
            state_root=state_dir,
            node_url=f"http://{args.host}:{args.port}",
            peer_url=replica_of_url,
            target=(
                HttpReplicaTarget(replica_target_url)
                if replica_target_url
                else None
            ),
        )
        server.replicator = replicator
        # before recover(): tenants the recovery walk activates must come
        # up with their WAL senders attached
        replication_holder["rep"] = replicator
        rep_summary = replicator.recover()
        # the default engine's sender (tenant engines attach via
        # tenant_engine_setup as they build)
        if journal is not None:
            replicator.attach_sender(DEFAULT_TENANT, engine)
        if replica_of_url and failover_after > 0:
            replicator.arm_failover(replica_of_url, after_s=failover_after)
        # epoch WAL compaction: a long promote/demote history folds to
        # one terminal record at boot and on soft disk pressure
        pressure_ctl.register_compactor(
            "epoch", replicator.compact_epoch_journal
        )
        if migrator is not None:
            # cross-plane wiring: a tenant cut over to another node must
            # stop shipping here AND be released on the standby, or a later
            # promotion resurrects the departed tenant's stale replica; a
            # tenant migrated back durably voids its release. Replay the
            # boot-recovered ownership verdicts through the same hooks
            # (migrator.recover() ran before the replicator existed).
            migrator.on_release = replicator.release_tenant
            migrator.on_adopt = replicator.adopt_tenant
            migrator.on_primacy_check = replicator.verify_primacy
            for tid in recovered.get("forwards", ()):
                fwd = tenants.forward_for(tid)
                if fwd:
                    replicator.release_tenant(tid, fwd[0], ship=False)
            for tid in recovered.get("owned", ()):
                replicator.adopt_tenant(tid, ship=False)
        replicator.start()
        log.info(
            "Replication role %s at epoch %d (%d protocol record(s) "
            "replayed); target %s, failover %s",
            replicator.role, replicator.epoch, rep_summary["records"],
            replica_target_url or "<none>",
            "%.1fs" % failover_after if failover_after > 0 else "manual",
        )
    install_drain_handlers(
        server,
        server.admission,
        log,
        # SIGTERM evacuates: migrate every resident tenant to the drain
        # target (or close it with a final WAL fold) under the bounded
        # deadline, then finalize the default engine's journal/batcher and
        # dump the span file — the satellite guarantee that shutdown folds
        # EVERY tenant, not just the default WAL
        on_drained=lambda: drain_supervisor.drain(reason="signal"),
    )
    # canary-gated hot reload: POST /patterns/reload re-reads this
    # directory (or takes inline YAML); --watch-patterns polls it
    from log_parser_tpu.runtime.reload import PatternReloader, PatternWatcher

    server.reloader = PatternReloader(
        engine,
        config.pattern_directory,
        lint_mode=os.environ.get("LOG_PARSER_TPU_LINT_PATTERNS", "warn"),
    )
    watch_s = float(os.environ.get("LOG_PARSER_TPU_WATCH_PATTERNS", "0"))
    if watch_s > 0:
        server.watcher = PatternWatcher(
            server.reloader, config.pattern_directory, interval_s=watch_s
        )
        server.watcher.start()
        log.info("Watching %s every %.1fs", config.pattern_directory, watch_s)
    if args.coordinator:
        # follower liveness probe + degraded-mesh readmission; serializes
        # with request broadcasts on the engine's state_lock
        engine.start_health_loop()

    # memory levers in severity order: cheapest/least-visible reclaim
    # first, each applied one poll apart while RSS stays over the
    # watermark, released in reverse once it clears (hysteresis)
    saved_knobs: dict = {}

    def _lever_line_cache() -> None:
        cache = getattr(engine, "line_cache", None)
        if cache is None:
            return
        saved_knobs["line_cache_bytes"] = cache.budget_bytes
        tenants.set_line_cache_budget(cache.budget_bytes // 2)

    def _release_line_cache() -> None:
        if "line_cache_bytes" in saved_knobs:
            tenants.set_line_cache_budget(
                saved_knobs.pop("line_cache_bytes")
            )

    def _lever_span_staging() -> None:
        spans = engine.obs.spans
        saved_knobs["staging_capacity"] = spans.staging_capacity
        spans.trim_staging(spans.staging_capacity // 2)

    def _release_span_staging() -> None:
        if "staging_capacity" in saved_knobs:
            engine.obs.spans.staging_capacity = saved_knobs.pop(
                "staging_capacity"
            )

    def _lever_miner_tap() -> None:
        m = getattr(engine, "miner", None)
        if m is not None:
            # the tap is the miner's only feed; closing it stops new
            # miss buffering (parked candidates stay reviewable)
            m.tap.close()

    pressure_ctl.add_lever(
        "line_cache", _lever_line_cache, _release_line_cache
    )
    pressure_ctl.add_lever("tenants", lambda: tenants.shed_idle(0.5))
    pressure_ctl.add_lever(
        "span_staging", _lever_span_staging, _release_span_staging
    )
    pressure_ctl.add_lever("miner_tap", _lever_miner_tap)
    pressure_ctl.bind_obs(engine.obs)
    pressure_ctl.bootstrap()
    pressure_ctl.start()
    if pressure_ctl.disk_soft_bytes or pressure_ctl.disk_hard_bytes or (
        pressure_ctl.mem_soft_bytes
    ):
        log.info(
            "Pressure plane armed: disk soft/hard %.0f/%.0f MB free, "
            "mem soft %.0f MB, retry budget %s",
            pressure_ctl.disk_soft_bytes / 2**20,
            pressure_ctl.disk_hard_bytes / 2**20,
            pressure_ctl.mem_soft_bytes / 2**20,
            "%.0f%%" % (pressure_ctl.retry.ratio * 100)
            if pressure_ctl.retry.enabled else "off",
        )
    log.info("Serving POST /parse on %s:%d", args.host, args.port)
    try:
        server.serve_forever()
        log.info("Drained; shutting down")
    except KeyboardInterrupt:  # pre-handler-install window only
        log.info("Shutting down")
    finally:
        server.server_close()
        drain_supervisor.stop_watch()
        if server.replicator is not None:
            # stop the pump + failover watch; the epoch journal closes
            # with its last fsynced record as the durable role
            server.replicator.stop()
        if server.watcher is not None:
            server.watcher.stop()
        # tenant engines first: closes their batchers/stream sessions and
        # folds each tenant WAL into a final snapshot, releasing any
        # shared-gate slots their sessions held
        server.tenants.shutdown()
        if server.stream_manager is not None:
            # kill open sessions so their admission slots release before
            # the gate's drain accounting is torn down
            server.stream_manager.shutdown()
        if engine.batcher is not None:
            # flush anything still queued before the process exits
            engine.batcher.close()
        if getattr(engine, "miner", None) is not None:
            # parked candidates are already durable on disk; this just
            # stops the worker and closes the tap
            engine.miner.stop()
        if engine.shadow is not None:
            engine.shadow.close()
        if journal is not None:
            # fold the WAL tail into one final durable snapshot — a clean
            # shutdown must never need replay on the next boot
            journal.snapshot_now()
            journal.close()
        if engine.obs.span_dump_path:
            try:
                if engine.obs.spans.dump(engine.obs.span_dump_path):
                    log.info(
                        "Span store dumped to %s", engine.obs.span_dump_path
                    )
                else:
                    # hard disk pressure: the dump skipped atomically —
                    # the least valuable bytes lose first, the drain
                    # completes either way
                    log.warning("span dump skipped: durability degraded")
            except OSError:
                log.exception("span dump failed")
        pressure_ctl.stop()
        pressure.install(None)
        if args.coordinator:
            # under the analyze lock: a daemon handler thread may still be
            # mid-broadcast inside analyze(); interleaving the shutdown
            # sentinel with a request broadcast would desync the followers
            with server.analyze_lock:
                engine.shutdown_followers()
    return 0


def _run_router(args, log) -> int:
    """Boot the fleet front-door (``--role router``): the HTTP proxy,
    the optional framed/gRPC fronts, and the placement control loop.
    No engine is constructed — the router is deliberately thin."""
    import threading

    from log_parser_tpu.fleet.budget import FleetBudget
    from log_parser_tpu.fleet.placement import FleetController
    from log_parser_tpu.fleet.router import (
        FramedRouterFront,
        make_grpc_front,
        make_router,
        parse_backends,
    )

    try:
        backends = parse_backends(args.backends or "")
    except ValueError as exc:
        log.error("%s", exc)
        return 2

    # the router rides the same pressure plane as a backend: the retry
    # budget bounds its re-route storms, and a --state-dir gives its
    # override journal a home plus disk watermarks over it
    from log_parser_tpu.runtime import faults, pressure

    faults.ensure_env()
    state_dir = os.environ.get("LOG_PARSER_TPU_STATE_DIR") or None
    pressure_ctl = pressure.PressureController(
        state_dir,
        disk_soft_mb=float(
            os.environ.get("LOG_PARSER_TPU_DISK_SOFT_MB", "0") or 0
        ),
        disk_hard_mb=float(
            os.environ.get("LOG_PARSER_TPU_DISK_HARD_MB", "0") or 0
        ),
        mem_soft_mb=float(
            os.environ.get("LOG_PARSER_TPU_MEM_SOFT_MB", "0") or 0
        ),
        retry_ratio=float(
            os.environ.get("LOG_PARSER_TPU_RETRY_BUDGET", "0.1") or 0
        ),
    )
    pressure.install(pressure_ctl)

    router = make_router(
        args.host, args.port, backends,
        vnodes=args.fleet_vnodes, down_after=args.fleet_down_after,
        state_dir=state_dir,
    )
    pressure_ctl.bind_obs(router.obs)
    pressure_ctl.bootstrap()
    pressure_ctl.start()

    budget = None
    if args.fleet_cache_mb > 0 or args.fleet_tenant_budget_mb > 0:
        budget = FleetBudget(args.fleet_cache_mb, args.fleet_tenant_budget_mb)
    controller = FleetController(
        router,
        poll_s=args.fleet_poll_s,
        burn_polls=args.fleet_burn_polls,
        shed_rate=args.fleet_shed_rate,
        thrash_rebuilds=args.fleet_thrash_rebuilds,
        move_cooldown_s=args.fleet_move_cooldown_s,
        budget=budget,
    )
    router.controller = controller

    framed = None
    grpc_front = None
    if args.backends_shim:
        shim_specs = [s.strip() for s in args.backends_shim.split(",")
                      if s.strip()]
        if len(shim_specs) != len(backends):
            log.error(
                "--backends-shim must list one host:port per --backends entry"
            )
            return 2
        if args.shim_port is None:
            log.error("--backends-shim requires --shim-port")
            return 2
        shim_addrs = {}
        for base, spec in zip(backends, shim_specs):
            host, _, port = spec.rpartition(":")
            try:
                shim_addrs[base] = (host or "127.0.0.1", int(port))
            except ValueError:
                log.error("bad --backends-shim entry %r: need host:port",
                          spec)
                return 2
        framed = FramedRouterFront(
            (args.host, args.shim_port), router, shim_addrs
        )
        router.framed_front = framed
        threading.Thread(
            target=framed.serve_forever, name="fleet-framed", daemon=True
        ).start()
        log.info("Framed front on %s:%d", args.host, args.shim_port)
        if args.grpc_port:
            grpc_front = make_grpc_front(
                router, framed, args.host, args.grpc_port
            )
            router.grpc_front = grpc_front
            if grpc_front is not None:
                log.info("gRPC front on %s:%d", args.host, args.grpc_port)
    elif args.grpc_port:
        log.error("--grpc-port on the router requires --backends-shim")
        return 2

    controller.start()
    log.info(
        "Fleet router on %s:%d -> %d backends (%d vnodes each)",
        args.host, args.port, len(backends), args.fleet_vnodes,
    )
    try:
        router.serve_forever()
    except KeyboardInterrupt:
        log.info("Shutting down router")
    finally:
        controller.stop()
        if grpc_front is not None:
            grpc_front.stop(grace=1.0)
        if framed is not None:
            framed.shutdown()
            framed.server_close()
        if router.override_journal is not None:
            router.override_journal.close()
        router.server_close()
        pressure_ctl.stop()
        pressure.install(None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
