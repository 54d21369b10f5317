"""Process-wide metrics registry: counters, gauges and fixed-bucket
histograms with bounded label cardinality, rendered as Prometheus text
exposition (version 0.0.4).

Two kinds of series feed one scrape:

* **Owned instruments** — hot-path counters/histograms the serving and
  runtime layers increment directly (request totals, per-phase latency,
  dropped responses). These are the single source of truth: the
  `/trace/last` fields that used to keep their own tallies (e.g.
  ``droppedResponses``) now *read* the registry instead of maintaining a
  parallel count.
* **Collector-backed series** — scrape-time callbacks that read the
  subsystems' existing ``stats()`` dicts (admission ladder, batcher,
  line cache, kernel tier, quarantine, shadow, miner, tenancy, streams)
  and re-emit them under stable metric names. No second copy of any
  counter exists, so ``/metrics`` and ``/trace/last`` agree bit-for-bit
  by construction: both are views over the same variables.

Every exported name must appear in :data:`METRICS` — hygiene check 16
pins each one to a backtick-quoted docs/OPS.md row, the same way checks
9/12/14 pin trace counters, tenancy and miner vocabularies.
"""

from __future__ import annotations

import threading

# name -> (type, help). The *only* metric-name vocabulary: instruments
# and collectors both refuse names missing from this table, and hygiene
# check 16 requires a docs/OPS.md row for every key. Keep it a plain
# dict literal — the checker harvests keys with ast, not an import.
METRICS = {
    # -------------------------------------------------- request plane
    "logparser_requests_total": (
        "counter", "Requests by transport, route, status and tenant."),
    "logparser_request_seconds": (
        "histogram", "End-to-end request wall latency by route."),
    "logparser_phase_seconds": (
        "histogram",
        "Per-phase engine latency fed by PhaseTrace, by tenant/phase/route."),
    "logparser_phase_cpu_seconds_total": (
        "counter",
        "Thread CPU seconds inside each timed PhaseTrace phase, by "
        "tenant/phase/route."),
    "logparser_stage_seconds": (
        "histogram",
        "Stages inside or between the phases (transport, device copies, "
        "frequency state), by tenant/stage."),
    "logparser_request_cpu_seconds_total": (
        "counter",
        "Thread CPU seconds of serving requests: each /parse handler from "
        "its start to after its write, plus batch flushes, by tenant/route."),
    "logparser_process_cpu_seconds_total": (
        "counter", "User plus system CPU seconds of the server process."),
    "logparser_slow_requests_total": (
        "counter",
        "Requests captured in the slow-trace ring (above --trace-slow-ms)."),
    "logparser_dropped_responses_total": (
        "counter",
        "Computed responses the transport failed to write, by transport."),
    "logparser_metric_series_overflow_total": (
        "counter",
        "Label sets folded into _overflow after an instrument's "
        "cardinality bound."),
    "logparser_profile_captures_total": (
        "counter", "Completed on-demand jax.profiler captures."),
    "logparser_slo_burn_rate": (
        "gauge", "SLO error-budget burn rate by objective and window."),
    # ---------------------------------------------- admission ladder
    "logparser_admission_total": (
        "counter", "Admission ladder outcomes (admitted and shed rungs)."),
    "logparser_inflight": (
        "gauge", "Requests currently holding an admission slot."),
    "logparser_admission_queued": (
        "gauge", "Requests parked in the admission queue."),
    # ------------------------------------------------------- engine
    "logparser_fallback_total": (
        "counter", "Requests served by the golden fallback after a "
        "device failure."),
    "logparser_host_routed_total": (
        "counter", "Requests deliberately routed to the vectorized "
        "host path."),
    "logparser_reload_epoch": ("gauge", "Pattern-bank reload epoch."),
    "logparser_device_circuit_open": (
        "gauge", "1 while the device watchdog circuit is open."),
    "logparser_quarantine_active": (
        "gauge", "Request fingerprints currently quarantined."),
    "logparser_quarantine_served_golden_total": (
        "counter", "Quarantined requests served straight from golden."),
    "logparser_shadow_divergences_total": (
        "counter", "Shadow-verification divergences."),
    "logparser_kernel_batches_total": (
        "counter", "Device dispatches by execution tier (kernel vs xla)."),
    "logparser_kernel_rows_total": (
        "counter", "Rows dispatched through the Pallas union-DFA kernel."),
    # ------------------------------------------------------- line cache
    "logparser_line_cache_hits_total": ("counter", "Line-cache hit lines."),
    "logparser_line_cache_misses_total": ("counter", "Line-cache miss lines."),
    "logparser_line_cache_evictions_total": (
        "counter", "Line-cache entries evicted."),
    "logparser_line_cache_resident_bytes": (
        "gauge", "Line-cache resident bytes."),
    "logparser_line_cache_probe_collisions_total": (
        "counter", "Line-cache keys whose probe led to a different line "
        "(looked up or stored as misses)."),
    "logparser_extract_hit_coords_total": (
        "counter", "(line, column) match-bit coordinates the line-cache "
        "extract carried, by tenant."),
    # ------------------------------------------------ sharded step
    "logparser_shard_relaunches_total": (
        "counter", "Line-sharded SPMD launches beyond a request's first "
        "(a shard overflowed its record bucket), by tenant."),
    "logparser_shard_exchange_bytes_total": (
        "counter", "Bytes the line-sharded step's halo ppermutes and "
        "all_gathers delivered between chips, by tenant."),
    "logparser_shard_record_slots_total": (
        "counter", "Per-shard record slots (shards x bucket) the "
        "line-sharded step read back, by tenant."),
    "logparser_shard_records_total": (
        "counter", "Live match records the line-sharded step read back, "
        "by tenant."),
    # ------------------------------------------------------ batcher
    "logparser_batch_queue_depth": (
        "gauge", "Requests parked in micro-batcher queues."),
    "logparser_requests_batched_total": (
        "counter", "Requests that rode a micro-batch."),
    "logparser_batches_flushed_total": (
        "counter", "Micro-batches flushed to the device."),
    # -------------------------------------------------------- miner
    "logparser_miner_tapped_total": (
        "counter", "Miss lines tapped into the template miner."),
    "logparser_miner_admitted_total": (
        "counter", "Mined patterns admitted into the serving bank."),
    # ------------------------------------------------------ tenancy
    "logparser_tenants_resident": (
        "gauge", "Tenant engines resident (including default)."),
    "logparser_tenant_builds_total": ("counter", "Tenant engine builds."),
    "logparser_tenant_evictions_total": (
        "counter", "Tenant engines evicted by the residency budget."),
    # ------------------------------------------------------ streams
    "logparser_stream_sessions": ("gauge", "Open streaming sessions."),
    "logparser_stream_chunks_total": (
        "counter", "Chunks ingested across streaming sessions."),
    "logparser_stream_frames_total": (
        "counter", "Frames emitted across streaming sessions."),
    # --------------------------------------------------- span store
    "logparser_trace_spans_total": (
        "counter", "Causal traces committed to the span store."),
    "logparser_trace_spans_dropped_total": (
        "counter", "Traces discarded by span sampling (children cleaned)."),
    # ------------------------------------- device utilization (roofline)
    "logparser_device_dispatches_total": (
        "counter", "Device dispatches by tenant and execution tier."),
    "logparser_device_padded_rows_total": (
        "counter", "Padded line rows shipped to the device (incl. waste)."),
    "logparser_device_dummy_rows_total": (
        "counter", "Dummy pow2-padding request slots dispatched (waste)."),
    "logparser_device_dummy_waste_ratio": (
        "gauge", "Dummy-slot waste fraction of the last batched dispatch."),
    # --------------------------------------- plan geometry + load state
    "logparser_kernel_plan_vmem_bytes": (
        "gauge", "Admitted union-DFA plan VMEM bytes per grid step."),
    "logparser_kernel_plan_groups": (
        "gauge", "Union-DFA groups in the admitted kernel plan."),
    "logparser_kernel_plan_plane_bytes": (
        "gauge", "Transition-plane bytes resident per kernel grid step."),
    "logparser_native_loaded": (
        "gauge", "1 when the native C++ scanner loaded; reason label "
        "carries the bounded load-failure class."),
    "logparser_compile_cache_events_total": (
        "counter", "Persistent XLA compile-cache events by kind (hit/miss)."),
    "logparser_journal_epoch": (
        "gauge", "Frequency-WAL snapshot epoch by tenant."),
    "logparser_lint_findings": (
        "gauge", "Findings in the last pattern-lint run by severity."),
    "logparser_faults_armed": (
        "gauge", "Fault-injection sites armed via LOG_PARSER_TPU_FAULTS."),
    "logparser_mesh_degraded": (
        "gauge", "1 while distributed serving is degraded to local."),
    # ---------------------------------------------- migration + drain
    "logparser_migration_total": (
        "counter",
        "Tenant-migration protocol outcomes by role and disposition "
        "(completed/aborted/staged/activated/recovered_*/session_*/"
        "drain_*)."),
    "logparser_migration_active": (
        "gauge", "Tenant migrations currently running the protocol."),
    "logparser_migration_forwards": (
        "gauge", "Tenants 307-forwarded to another process post-cutover."),
    "logparser_migration_draining": (
        "gauge", "1 while the drain supervisor is evacuating this process."),
    # ------------------------------------------------- replication
    "logparser_replication_lag_records": (
        "gauge", "Whole WAL records fsynced on the primary but not yet "
        "acked by the standby, per tenant."),
    "logparser_replication_lag_bytes": (
        "gauge", "WAL bytes past the standby's acked offset, per tenant."),
    "logparser_replication_lag_seconds": (
        "gauge", "Age of the oldest un-acked WAL record, per tenant."),
    "logparser_replication_acked_offset": (
        "gauge", "Replication byte offset acked per tenant, by side "
        "(sender/receiver)."),
    "logparser_replication_epoch": (
        "gauge", "Ownership epoch this process last journaled; role label "
        "says primary or standby."),
    "logparser_replication_total": (
        "counter", "Replication batch outcomes "
        "(shipped/applied/rejected/reseed/send_error)."),
    "logparser_replication_promotions_total": (
        "counter", "Fenced ownership transitions journaled by this "
        "process (kind=promote/demote)."),
    # ------------------------------------------------------- fleet
    "logparser_fleet_routed_total": (
        "counter", "Router-proxied requests by backend and outcome."),
    "logparser_fleet_reroutes_total": (
        "counter", "Ring re-routes by reason (forward/backend_down)."),
    "logparser_fleet_backends_up": (
        "gauge", "Backends currently on the router's ring."),
    "logparser_fleet_overrides": (
        "gauge", "Per-tenant ring overrides installed on the router."),
    "logparser_fleet_moves_total": (
        "counter", "Placer-initiated live tenant moves by trigger "
        "(quota_shed/slo_burn/residency_thrash)."),
    "logparser_fleet_budget_mb": (
        "gauge", "Fleet-arbitrated budget share by backend and kind "
        "(line_cache/tenant)."),
    # ---------------------------------------------------- pressure
    "logparser_pressure_state": (
        "gauge", "Resource-pressure ladder rung per resource "
        "(0=ok, 1=soft, 2=hard)."),
    "logparser_pressure_transitions_total": (
        "counter", "Pressure ladder transitions by resource and "
        "entered state."),
    "logparser_pressure_degraded_writes_total": (
        "counter", "WAL records absorbed by in-memory rings while disk "
        "durability is degraded."),
    "logparser_pressure_levers_total": (
        "counter", "Memory-pressure lever pulls by lever name."),
    "logparser_pressure_retry_total": (
        "counter", "Retry-budget verdicts by outcome (allowed/shed)."),
}

# /trace/last payload block -> covering /metrics families. Hygiene
# check 16 harvests every ``payload["..."]`` key assigned in
# serve/http.py and fails when a block is missing here or maps to a
# name outside METRICS — so a new trace block cannot ship invisible to
# scrapers again (the PR-10 native block did exactly that).
TRACE_BLOCKS = {
    "phasesMs": ("logparser_phase_seconds",),
    "totalMs": ("logparser_request_seconds",),
    "fallbackCount": ("logparser_fallback_total",),
    "hostRoutedCount": ("logparser_host_routed_total",),
    "deviceCircuitOpen": ("logparser_device_circuit_open",),
    "droppedResponses": ("logparser_dropped_responses_total",),
    "admission": ("logparser_admission_total", "logparser_inflight",
                  "logparser_admission_queued"),
    "traceRing": ("logparser_slow_requests_total",),
    "spans": ("logparser_trace_spans_total",
              "logparser_trace_spans_dropped_total"),
    "batcher": ("logparser_batch_queue_depth",
                "logparser_requests_batched_total",
                "logparser_batches_flushed_total"),
    "lineCache": ("logparser_line_cache_hits_total",
                  "logparser_line_cache_misses_total",
                  "logparser_line_cache_evictions_total",
                  "logparser_line_cache_resident_bytes",
                  "logparser_line_cache_probe_collisions_total"),
    "kernel": ("logparser_kernel_batches_total",
               "logparser_kernel_rows_total",
               "logparser_kernel_plan_vmem_bytes",
               "logparser_kernel_plan_groups",
               "logparser_kernel_plan_plane_bytes"),
    "distributed": ("logparser_mesh_degraded",),
    "journal": ("logparser_journal_epoch",),
    "stream": ("logparser_stream_sessions",
               "logparser_stream_chunks_total",
               "logparser_stream_frames_total"),
    "native": ("logparser_native_loaded",),
    "compileCache": ("logparser_compile_cache_events_total",),
    "quarantine": ("logparser_quarantine_active",
                   "logparser_quarantine_served_golden_total"),
    "miner": ("logparser_miner_tapped_total",
              "logparser_miner_admitted_total"),
    "shadow": ("logparser_shadow_divergences_total",),
    "reload": ("logparser_reload_epoch",),
    "lint": ("logparser_lint_findings",),
    "tenants": ("logparser_tenants_resident",
                "logparser_tenant_builds_total",
                "logparser_tenant_evictions_total"),
    "faults": ("logparser_faults_armed",),
    "migration": ("logparser_migration_total",
                  "logparser_migration_active",
                  "logparser_migration_forwards",
                  "logparser_migration_draining"),
    "replication": ("logparser_replication_lag_records",
                    "logparser_replication_lag_bytes",
                    "logparser_replication_lag_seconds",
                    "logparser_replication_acked_offset",
                    "logparser_replication_epoch",
                    "logparser_replication_total",
                    "logparser_replication_promotions_total"),
    "pressure": ("logparser_pressure_state",
                 "logparser_pressure_transitions_total",
                 "logparser_pressure_degraded_writes_total",
                 "logparser_pressure_levers_total",
                 "logparser_pressure_retry_total"),
}

# request latency: sub-ms cache hits through multi-second cold compiles
DEFAULT_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)

# per-instrument child bound; beyond it new label sets fold into a
# single reserved series so a tenant-id flood cannot OOM the registry
DEFAULT_MAX_SERIES = 64
OVERFLOW_LABEL = "_overflow"

_INF = float("inf")


def _escape(value: str) -> str:
    return (
        str(value).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    )


def _labels_text(labelnames: tuple, labelvalues: tuple) -> str:
    if not labelnames:
        return ""
    inner = ",".join(
        f'{k}="{_escape(v)}"' for k, v in zip(labelnames, labelvalues)
    )
    return "{" + inner + "}"


class _Instrument:
    """One named metric family: a dict of label-tuple -> child state
    behind one lock. ``inc``/``set``/``observe`` are a lock, a dict
    lookup and an add — cheap enough for the request hot path."""

    kind = "untyped"

    def __init__(self, name: str, labelnames: tuple[str, ...],
                 max_series: int, registry: "Registry"):
        self.name = name
        self.labelnames = tuple(labelnames)
        self.max_series = max_series
        self._registry = registry
        self._lock = threading.Lock()
        self._children: dict[tuple, object] = {}

    def _key(self, labels: dict) -> tuple:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: got labels {sorted(labels)}, "
                f"declared {sorted(self.labelnames)}"
            )
        return tuple(str(labels[k]) for k in self.labelnames)

    def _child(self, key: tuple):
        # caller holds self._lock
        child = self._children.get(key)
        if child is None:
            if len(self._children) >= self.max_series:
                over = (OVERFLOW_LABEL,) * len(self.labelnames)
                child = self._children.get(over)
                if child is None:
                    child = self._new_child()
                    self._children[over] = child
                self._registry.note_overflow()
                return child
            child = self._new_child()
            self._children[key] = child
        return child

    def _new_child(self):  # pragma: no cover - overridden
        raise NotImplementedError

    def series(self) -> list[tuple[tuple, object]]:
        with self._lock:
            return list(self._children.items())


class Counter(_Instrument):
    kind = "counter"

    def _new_child(self):
        return [0.0]

    def inc(self, amount: float = 1.0, **labels) -> None:
        key = self._key(labels)
        with self._lock:
            self._child(key)[0] += amount

    def value(self, **labels) -> float:
        key = self._key(labels)
        with self._lock:
            child = self._children.get(key)
            return child[0] if child is not None else 0.0

    def total(self) -> float:
        with self._lock:
            return sum(c[0] for c in self._children.values())


class Gauge(_Instrument):
    kind = "gauge"

    def _new_child(self):
        return [0.0]

    def set(self, value: float, **labels) -> None:
        key = self._key(labels)
        with self._lock:
            self._child(key)[0] = float(value)

    def value(self, **labels) -> float:
        key = self._key(labels)
        with self._lock:
            child = self._children.get(key)
            return child[0] if child is not None else 0.0


class _HistChild:
    __slots__ = ("counts", "sum", "count")

    def __init__(self, n_buckets: int):
        self.counts = [0] * n_buckets  # per-bucket (non-cumulative)
        self.sum = 0.0
        self.count = 0


class Histogram(_Instrument):
    kind = "histogram"

    def __init__(self, name, labelnames, max_series, registry,
                 buckets=DEFAULT_BUCKETS):
        super().__init__(name, labelnames, max_series, registry)
        bounds = tuple(sorted(float(b) for b in buckets))
        if not bounds:
            raise ValueError(f"{name}: histogram needs at least one bucket")
        self.bounds = bounds  # +Inf is implicit

    def _new_child(self):
        return _HistChild(len(self.bounds) + 1)

    def observe(self, value: float, **labels) -> None:
        key = self._key(labels)
        # bisect by hand: bounds are short tuples, and `le` is inclusive
        idx = len(self.bounds)
        for i, b in enumerate(self.bounds):
            if value <= b:
                idx = i
                break
        with self._lock:
            child = self._child(key)
            child.counts[idx] += 1
            child.sum += value
            child.count += 1

    def snapshot(self, **labels) -> tuple[list[int], float, int]:
        """(cumulative bucket counts incl. +Inf, sum, count)."""
        key = self._key(labels)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                return [0] * (len(self.bounds) + 1), 0.0, 0
            cum, running = [], 0
            for c in child.counts:
                running += c
                cum.append(running)
            return cum, child.sum, child.count


class Registry:
    """Instrument factory + scrape renderer. ``counter``/``gauge``/
    ``histogram`` are idempotent by name so independent call sites can
    share a family; collectors are keyed and replaced on re-register so
    server restarts over one engine never double-emit."""

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments: dict[str, _Instrument] = {}
        self._collectors: dict[str, object] = {}
        self._overflow = self.counter("logparser_metric_series_overflow_total")

    # ------------------------------------------------------- factories

    def _make(self, cls, name, labelnames, max_series, **kw):
        if name not in METRICS:
            raise ValueError(f"metric {name!r} is not declared in METRICS")
        if METRICS[name][0] != cls.kind:
            raise ValueError(
                f"metric {name!r} is declared {METRICS[name][0]}, "
                f"not {cls.kind}"
            )
        with self._lock:
            inst = self._instruments.get(name)
            if inst is None:
                inst = cls(name, tuple(labelnames), max_series, self, **kw)
                self._instruments[name] = inst
            elif not isinstance(inst, cls) or inst.labelnames != tuple(labelnames):
                raise ValueError(f"metric {name!r} re-declared differently")
            return inst

    def counter(self, name, labelnames=(), max_series=DEFAULT_MAX_SERIES):
        return self._make(Counter, name, labelnames, max_series)

    def gauge(self, name, labelnames=(), max_series=DEFAULT_MAX_SERIES):
        return self._make(Gauge, name, labelnames, max_series)

    def histogram(self, name, labelnames=(), buckets=DEFAULT_BUCKETS,
                  max_series=DEFAULT_MAX_SERIES):
        return self._make(Histogram, name, labelnames, max_series,
                          buckets=buckets)

    def note_overflow(self) -> None:
        # called while the overflowing instrument's own lock is held;
        # the overflow counter's lock is distinct and never re-enters
        with self._overflow._lock:
            child = self._overflow._children.get(())
            if child is None:
                child = self._overflow._children[()] = [0.0]
            child[0] += 1

    # ------------------------------------------------------ collectors

    def register_collector(self, key: str, fn) -> None:
        """``fn() -> iterable of (metric_name, labels_dict, value)``.
        Runs at scrape time; replaced when ``key`` re-registers."""
        with self._lock:
            self._collectors[key] = fn

    def unregister_collector(self, key: str) -> None:
        with self._lock:
            self._collectors.pop(key, None)

    def _collected(self) -> dict[str, list[tuple[dict, float]]]:
        with self._lock:
            fns = list(self._collectors.values())
        out: dict[str, list[tuple[dict, float]]] = {}
        for fn in fns:
            try:
                samples = list(fn())
            except Exception:
                continue  # a broken subsystem must not take down /metrics
            for name, labels, value in samples:
                if name not in METRICS:
                    continue
                out.setdefault(name, []).append((dict(labels), float(value)))
        return out

    # --------------------------------------------------------- scrape

    def render(self) -> str:
        """Prometheus text exposition, family order pinned to METRICS."""
        collected = self._collected()
        with self._lock:
            owned = dict(self._instruments)
        lines: list[str] = []
        for name, (kind, help_text) in METRICS.items():
            inst = owned.get(name)
            extra = collected.get(name)
            if inst is None and not extra:
                continue
            lines.append(f"# HELP {name} {help_text}")
            lines.append(f"# TYPE {name} {kind}")
            if isinstance(inst, Histogram):
                for key, _child in sorted(inst.series()):
                    labels = dict(zip(inst.labelnames, key))
                    cum, total, count = inst.snapshot(**labels)
                    for bound, c in zip(
                        list(inst.bounds) + [_INF], cum
                    ):
                        le = "+Inf" if bound == _INF else repr(bound)
                        ltext = _labels_text(
                            inst.labelnames + ("le",), key + (le,)
                        )
                        lines.append(f"{name}_bucket{ltext} {c}")
                    ltext = _labels_text(inst.labelnames, key)
                    lines.append(f"{name}_sum{ltext} {_fmt(total)}")
                    lines.append(f"{name}_count{ltext} {count}")
            elif inst is not None:
                for key, child in sorted(inst.series()):
                    ltext = _labels_text(inst.labelnames, key)
                    lines.append(f"{name}{ltext} {_fmt(child[0])}")
            if extra:
                for labels, value in sorted(
                    extra, key=lambda s: sorted(s[0].items())
                ):
                    names = tuple(sorted(labels))
                    ltext = _labels_text(
                        names, tuple(labels[k] for k in names)
                    )
                    lines.append(f"{name}{ltext} {_fmt(value)}")
        return "\n".join(lines) + "\n"

    # ----------------------------------------------------- test/view

    def value(self, name: str, **labels) -> float:
        with self._lock:
            inst = self._instruments.get(name)
        if isinstance(inst, (Counter, Gauge)):
            return inst.value(**labels)
        return 0.0

    def total(self, name: str) -> float:
        with self._lock:
            inst = self._instruments.get(name)
        if isinstance(inst, Counter):
            return inst.total()
        return 0.0

    def collected_value(self, name: str, **labels) -> float | None:
        """Scrape-time value of a collector-backed series (tests)."""
        for got, value in self._collected().get(name, []):
            if got == {k: str(v) for k, v in labels.items()} or got == labels:
                return value
        return None


def _fmt(v: float) -> str:
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(v)


def samples_from_stats(stats: dict, spec, labels: dict | None = None):
    """Map a subsystem ``stats()`` dict onto registry samples.

    ``spec`` rows are ``(stats_key, metric_name, extra_labels)``; the
    subsystems keep their spec next to their ``stats()`` method so the
    mapping and the source stay in one diff."""
    base = labels or {}
    out = []
    for stats_key, metric, extra in spec:
        value = stats.get(stats_key)
        if value is None:
            continue
        out.append((metric, {**base, **extra}, float(value)))
    return out
