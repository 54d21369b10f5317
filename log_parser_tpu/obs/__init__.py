"""Fleet observability plane: one :class:`Obs` bundle per engine family
ties together the metrics registry (:mod:`.registry`), the request-trace
ring (:mod:`.ring`), SLO burn accounting (:mod:`.slo`) and on-demand
device profiling (:mod:`.profiler`).

The bundle is rooted at the engine (``engine.obs``) rather than being a
process singleton: every transport (HTTP, framed shim, gRPC, streaming)
already holds the engine, tenant engines share the primary's bundle
under their own ``tenant`` label, and each test engine gets fresh
zeroed counters instead of cross-test pollution. Configuration comes
from the same env vars the serve flags mirror, read once per bundle."""

from __future__ import annotations

import os
import time
import uuid

from log_parser_tpu import _clock as pclock
from log_parser_tpu.obs.profiler import (  # noqa: F401  (re-export)
    DeviceProfiler,
    ProfilerBusy,
    ProfilerUnavailable,
)
from log_parser_tpu.obs.registry import (  # noqa: F401  (re-export)
    METRICS,
    Registry,
    samples_from_stats,
)
from log_parser_tpu.obs.ring import DEFAULT_CAPACITY, DEFAULT_SLOW_MS, TraceRing
from log_parser_tpu.obs.slo import (
    DEFAULT_BURN_THRESHOLD,
    DEFAULT_WINDOWS_S,
    SloTracker,
)
from log_parser_tpu.obs.spans import (  # noqa: F401  (re-export)
    DEFAULT_SPAN_CAPACITY,
    SPANS,
    SpanStore,
)

# finer low end than the request histogram: cache-hit phases are sub-ms
PHASE_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
)

# engine-attribute samples every engine collector emits; subsystems with
# their own stats() dicts keep their spec next to that method instead
# (serve/admission.py, runtime/{batcher,linecache,stream,tenancy}.py)
_QUARANTINE_SAMPLES = (
    ("active", "logparser_quarantine_active", {}),
    ("servedGolden", "logparser_quarantine_served_golden_total", {}),
)
_SHADOW_SAMPLES = (
    ("divergences", "logparser_shadow_divergences_total", {}),
)
_MINER_SAMPLES = (
    ("tapped", "logparser_miner_tapped_total", {}),
    ("admitted", "logparser_miner_admitted_total", {}),
)
_JOURNAL_SAMPLES = (
    ("epoch", "logparser_journal_epoch", {}),
)

# bounded reason classes for logparser_native_loaded, matched against
# the load-failure string native.stats() records (native/__init__.py
# sets _load_error exactly once) — the label stays low-cardinality no
# matter what the dlopen error text says
_NATIVE_REASONS = (
    ("disabled", "disabled"),
    ("compile failed", "compile_failed"),
    ("no prebuilt library", "no_library"),
    # before the generic dlopen bucket: native/__init__.py diagnoses the
    # built-on-a-newer-distro case (required GLIBCXX symbol versions the
    # host libstdc++ doesn't export) and prefixes it distinctly, so the
    # scrape can alert on it specifically (tools/check_native.py prints
    # the full required-vs-provided table)
    ("glibcxx mismatch", "glibcxx_mismatch"),
    ("load failed", "load_failed"),
    ("stale library", "stale"),
)


def native_load_reason(stats: dict) -> str:
    """Map native.stats() onto the bounded ``reason`` label vocabulary
    (ok / not_loaded / disabled / compile_failed / no_library /
    glibcxx_mismatch / load_failed / stale / other)."""
    if stats.get("available"):
        return "ok"
    err = stats.get("loadError")
    if not err:
        return "not_loaded"
    for prefix, reason in _NATIVE_REASONS:
        if err.startswith(prefix):
            return reason
    return "other"


def _native_samples():
    """`logparser_native_loaded` — the GLIBCXX triage that used to live
    only on /trace/last, now scrapeable (lazy import: get_lib is warmed
    by boot, a scrape never triggers a compile)."""
    from log_parser_tpu import native

    st = native.stats()
    return [(
        "logparser_native_loaded",
        {"reason": native_load_reason(st)},
        1.0 if st.get("available") else 0.0,
    )]


def _compile_cache_samples():
    from log_parser_tpu.utils import xlacache

    st = xlacache.stats()
    return [
        ("logparser_compile_cache_events_total", {"kind": "hit"},
         st.get("compileHits", 0)),
        ("logparser_compile_cache_events_total", {"kind": "miss"},
         st.get("compileMisses", 0)),
    ]


def _fault_samples():
    from log_parser_tpu.runtime import faults

    st = faults.stats()
    armed = 0 if st is None else len(st.get("fired", {}))
    return [("logparser_faults_armed", {}, armed)]


def _process_cpu_samples():
    t = os.times()
    return [("logparser_process_cpu_seconds_total", {}, t.user + t.system)]


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


class Obs:
    """Registry + trace ring + SLO tracker + profiler for one engine
    family. Cheap to construct (no threads, no jax imports)."""

    def __init__(self, clock=pclock.mono):
        self.registry = Registry()
        self.ring = TraceRing(
            capacity=int(
                _env_float("LOG_PARSER_TPU_TRACE_RING", DEFAULT_CAPACITY)
            ),
            slow_ms=_env_float("LOG_PARSER_TPU_TRACE_SLOW_MS", DEFAULT_SLOW_MS),
        )
        windows = tuple(
            float(w)
            for w in os.environ.get("LOG_PARSER_TPU_SLO_WINDOWS_S", "").split(",")
            if w.strip()
        ) or DEFAULT_WINDOWS_S
        self.slo = SloTracker(
            p99_ms=_env_float("LOG_PARSER_TPU_SLO_P99_MS", 0.0),
            availability=_env_float("LOG_PARSER_TPU_SLO_AVAILABILITY", 0.0),
            windows_s=windows,
            burn_threshold=_env_float(
                "LOG_PARSER_TPU_SLO_BURN", DEFAULT_BURN_THRESHOLD
            ),
            clock=clock,
        )
        self.profiler = DeviceProfiler(on_complete=self._profile_done)
        self.spans = SpanStore(
            capacity=int(
                _env_float("LOG_PARSER_TPU_TRACE_SPANS", DEFAULT_SPAN_CAPACITY)
            ),
            sample=_env_float("LOG_PARSER_TPU_TRACE_SAMPLE", 1.0),
            slow_ms=self.ring.slow_ms,
        )
        self.span_dump_path: str | None = None
        self.clock = clock
        reg = self.registry
        self.requests_total = reg.counter(
            "logparser_requests_total",
            ("transport", "route", "status", "tenant"),
            max_series=256,
        )
        self.request_seconds = reg.histogram(
            "logparser_request_seconds", ("route",)
        )
        self.phase_seconds = reg.histogram(
            "logparser_phase_seconds", ("tenant", "phase", "route"),
            buckets=PHASE_BUCKETS, max_series=256,
        )
        self.phase_cpu = reg.counter(
            "logparser_phase_cpu_seconds_total", ("tenant", "phase", "route"),
            max_series=256,
        )
        self.stage_seconds = reg.histogram(
            "logparser_stage_seconds", ("tenant", "stage"),
            buckets=PHASE_BUCKETS, max_series=256,
        )
        self.request_cpu = reg.counter(
            "logparser_request_cpu_seconds_total", ("tenant", "route"),
            max_series=256,
        )
        self.slow_requests = reg.counter(
            "logparser_slow_requests_total", ("route",)
        )
        self.dropped = reg.counter(
            "logparser_dropped_responses_total", ("transport",)
        )
        self.profile_captures = reg.counter("logparser_profile_captures_total")
        self.device_dispatches = reg.counter(
            "logparser_device_dispatches_total", ("tenant", "tier"),
            max_series=128,
        )
        self.device_padded_rows = reg.counter(
            "logparser_device_padded_rows_total", ("tenant",)
        )
        self.device_dummy_rows = reg.counter(
            "logparser_device_dummy_rows_total", ("tenant",)
        )
        self.device_waste = reg.gauge(
            "logparser_device_dummy_waste_ratio", ("tenant",)
        )
        self.extract_hit_coords = reg.counter(
            "logparser_extract_hit_coords_total", ("tenant",)
        )
        self.shard_relaunches = reg.counter(
            "logparser_shard_relaunches_total", ("tenant",)
        )
        self.shard_exchange_bytes = reg.counter(
            "logparser_shard_exchange_bytes_total", ("tenant",)
        )
        self.shard_record_slots = reg.counter(
            "logparser_shard_record_slots_total", ("tenant",)
        )
        self.shard_records = reg.counter(
            "logparser_shard_records_total", ("tenant",)
        )
        reg.register_collector("slo", self.slo.samples)
        reg.register_collector("spans", self._span_samples)
        reg.register_collector("native", _native_samples)
        reg.register_collector("compilecache", _compile_cache_samples)
        reg.register_collector("faults", _fault_samples)
        reg.register_collector("process", _process_cpu_samples)

    def _span_samples(self):
        st = self.spans.stats()
        return [
            ("logparser_trace_spans_total", {}, st["committed"]),
            ("logparser_trace_spans_dropped_total", {}, st["droppedTraces"]),
        ]

    def _profile_done(self) -> None:
        self.profile_captures.inc()

    # ------------------------------------------------------- identity

    @staticmethod
    def new_request_id() -> str:
        return uuid.uuid4().hex[:16]

    @staticmethod
    def clean_request_id(raw: str | None) -> str | None:
        """Sanitize an inbound X-Request-Id: printable, bounded, no
        header/label injection."""
        if not raw:
            return None
        rid = "".join(c for c in raw.strip() if c.isprintable())[:128]
        return rid or None

    # ------------------------------------------------------- hot path

    def note_served(self, trace, start: float, tenant: str,
                    outcome: str = "ok", n_lines: int | None = None,
                    error: str | None = None) -> None:
        """One engine-served request: phase and stage histograms, phase
        CPU + ring entry. Called from ``_finish`` (and the fallback path)
        with the request's :class:`PhaseTrace`."""
        route = getattr(trace, "route", "device") or "device"
        request_id = getattr(trace, "request_id", None) or self.new_request_id()
        total_ms = (self.clock() - start) * 1e3
        phases = trace.as_dict()
        observe = self.phase_seconds.observe
        for phase, seconds in phases.items():
            observe(seconds, tenant=tenant, phase=phase, route=route)
        for phase, seconds in trace.cpu_dict().items():
            self.phase_cpu.inc(seconds, tenant=tenant, phase=phase, route=route)
        self.note_stages(trace.stage_dict(), tenant)
        entry = {
            "requestId": request_id,
            "tenant": tenant,
            "route": route,
            "outcome": outcome,
            "totalMs": round(total_ms, 3),
            "phasesMs": {k: round(v * 1e3, 3) for k, v in phases.items()},
        }
        if n_lines is not None:
            entry["lines"] = n_lines
        if error is not None:
            entry["error"] = error
        if self.ring.record(entry):
            self.slow_requests.inc(route=route)
        # the span root is built from the SAME clock delta and phases
        # dict as the ring entry + phase histograms above, so the three
        # surfaces reconcile exactly, not approximately
        attrs = {"route": route, "outcome": outcome}
        if n_lines is not None:
            attrs["lines"] = n_lines
        if error is not None:
            attrs["error"] = error
        extra = getattr(trace, "span_attrs", None)
        if extra:
            attrs.update(extra)
        self.spans.end_trace(
            request_id, duration_s=total_ms / 1e3, tenant=tenant,
            attrs=attrs, phases=phases,
            links=list(getattr(trace, "links", ()) or ()),
        )

    def note_request(self, transport: str, route: str, status: int,
                     tenant: str, duration_s: float,
                     request_id: str | None = None,
                     detail: str | None = None) -> None:
        """One transport-level request outcome: totals, latency, SLO.
        Ring entries for non-200 outcomes (200s were already recorded by
        the engine with full phase detail)."""
        self.requests_total.inc(
            transport=transport, route=route, status=str(status),
            tenant=tenant,
        )
        self.request_seconds.observe(duration_s, route=route)
        self.slo.note(ok=status < 500, duration_ms=duration_s * 1e3)
        if status != 200:
            entry = {
                "requestId": request_id or self.new_request_id(),
                "tenant": tenant,
                "route": route,
                "outcome": f"http_{status}" if transport == "http"
                else f"{transport}_{status}",
                "totalMs": round(duration_s * 1e3, 3),
                "phasesMs": {},
            }
            if detail:
                entry["error"] = detail
            if self.ring.record(entry):
                self.slow_requests.inc(route=route)
            # non-200s never reach note_served, so their trace (and any
            # staged admission child) must be finished here — otherwise
            # a shed request would orphan its staged spans
            attrs = {"route": route, "outcome": entry["outcome"],
                     "transport": transport, "status": status}
            if detail:
                attrs["error"] = detail
            self.spans.end_trace(
                entry["requestId"], duration_s=duration_s, tenant=tenant,
                attrs=attrs,
            )

    def note_stages(self, stages: dict[str, float], tenant: str) -> None:
        """One observation per stage of one request: the engine's
        through :meth:`note_served`, the transport's from the handler
        once the response is written."""
        observe = self.stage_seconds.observe
        for stage, seconds in stages.items():
            observe(seconds, tenant=tenant, stage=stage)

    def note_request_cpu(self, seconds: float, tenant: str,
                         route: str) -> None:
        """Thread CPU the server spent on requests: a ``/parse``
        handler's, from its start to after its write, or a batch flush's
        on the scheduler thread (``route`` ``batched``)."""
        self.request_cpu.inc(seconds, tenant=tenant, route=route)

    def note_dispatch(self, tenant: str, tier: str, padded_rows: int = 0,
                      dummy_rows: int = 0,
                      waste: float | None = None) -> None:
        """Per-dispatch device-utilization accounting: every device
        step (direct, batched flush, line-cache residual) folds its
        padded and dummy rows into the per-tenant ``logparser_device_*``
        families."""
        self.device_dispatches.inc(tenant=tenant, tier=tier)
        if padded_rows:
            self.device_padded_rows.inc(padded_rows, tenant=tenant)
        if dummy_rows:
            self.device_dummy_rows.inc(dummy_rows, tenant=tenant)
        if waste is not None:
            self.device_waste.set(waste, tenant=tenant)

    def note_extract_hits(self, coords: int, tenant: str) -> None:
        """One line-cache extract: the ``(line, col)`` hit coordinates it
        carried. Over lines × columns, the hit density its cost follows."""
        self.extract_hit_coords.inc(coords, tenant=tenant)

    def note_shard_step(self, tenant: str, relaunches: int,
                        exchange_bytes: int, record_slots: int,
                        records: int) -> None:
        """One request through the line-sharded SPMD step
        (parallel/sharded.py): its launches beyond the first (K-ladder
        overflows), the bytes its launches' collectives delivered between
        chips, and the record slots read back against the live records."""
        self.shard_relaunches.inc(relaunches, tenant=tenant)
        self.shard_exchange_bytes.inc(exchange_bytes, tenant=tenant)
        self.shard_record_slots.inc(record_slots, tenant=tenant)
        self.shard_records.inc(records, tenant=tenant)

    def note_dropped(self, transport: str) -> None:
        """A computed response the transport could not write back —
        the one counter shared by HTTP, framed shim and gRPC."""
        self.dropped.inc(transport=transport)

    @property
    def dropped_responses(self) -> int:
        return int(self.dropped.total())

    # ----------------------------------------------------- collectors

    def add_engine_collector(self, engine) -> None:
        """Scrape-time view over one engine's counters and its enabled
        subsystems' ``stats()`` dicts (line cache, batcher,
        kernel tier, quarantine, shadow, miner)."""

        def collect():
            tenant = getattr(engine, "obs_tenant", "default")
            labels = {"tenant": tenant}
            out = [
                ("logparser_fallback_total", labels,
                 getattr(engine, "fallback_count", 0)),
                ("logparser_host_routed_total", labels,
                 getattr(engine, "host_routed_count", 0)),
                ("logparser_reload_epoch", labels,
                 getattr(engine, "reload_epoch", 0)),
            ]
            watchdog = getattr(engine, "watchdog", None)
            if watchdog is not None:
                out.append((
                    "logparser_device_circuit_open", labels,
                    1.0 if watchdog.circuit_open else 0.0,
                ))
            kernel = getattr(engine, "kernel_stats", None)
            if kernel is not None:
                ks = kernel.stats()
                out.extend([
                    ("logparser_kernel_batches_total",
                     {**labels, "tier": "kernel"}, ks.get("kernelBatches", 0)),
                    ("logparser_kernel_batches_total",
                     {**labels, "tier": "xla"}, ks.get("xlaBatches", 0)),
                    ("logparser_kernel_rows_total", labels,
                     ks.get("kernelRows", 0)),
                ])
                geometry = ks.get("geometry") or {}
                if geometry:
                    out.extend([
                        ("logparser_kernel_plan_vmem_bytes", labels,
                         geometry.get("vmemPerStep", 0)),
                        ("logparser_kernel_plan_groups", labels,
                         geometry.get("nGroups", 0)),
                        ("logparser_kernel_plan_plane_bytes", labels,
                         geometry.get("planeBytes", 0)),
                    ])
            journal = getattr(engine, "journal", None)
            if journal is not None:
                out.extend(samples_from_stats(
                    journal.stats(), _JOURNAL_SAMPLES, labels
                ))
            last_lint = getattr(engine, "last_lint", None)
            if last_lint:
                for severity in ("error", "warn", "info"):
                    if severity in last_lint:
                        out.append((
                            "logparser_lint_findings",
                            {**labels, "severity": severity},
                            last_lint[severity],
                        ))
            mesh = getattr(engine, "mesh_health", None)
            if mesh is not None:
                out.append((
                    "logparser_mesh_degraded", labels,
                    0.0 if mesh.stats().get("mode") == "distributed" else 1.0,
                ))
            quarantine = getattr(engine, "quarantine", None)
            if quarantine is not None:
                out.extend(samples_from_stats(
                    quarantine.stats(), _QUARANTINE_SAMPLES, labels
                ))
            shadow = getattr(engine, "shadow", None)
            if shadow is not None:
                out.extend(samples_from_stats(
                    shadow.stats(), _SHADOW_SAMPLES, labels
                ))
            miner = getattr(engine, "miner", None)
            if miner is not None:
                out.extend(samples_from_stats(
                    miner.stats(), _MINER_SAMPLES, labels
                ))
            cache = getattr(engine, "line_cache", None)
            if cache is not None:
                from log_parser_tpu.runtime import linecache as lc

                out.extend(samples_from_stats(
                    cache.stats(), lc.CACHE_METRIC_SAMPLES, labels
                ))
            batcher = getattr(engine, "batcher", None)
            if batcher is not None:
                from log_parser_tpu.runtime import batcher as bt

                out.extend(samples_from_stats(
                    batcher.stats(), bt.METRIC_SAMPLES, labels
                ))
            return out

        self.registry.register_collector(f"engine-{id(engine)}", collect)

    def remove_engine_collector(self, engine) -> None:
        self.registry.unregister_collector(f"engine-{id(engine)}")

    def add_stats_collector(self, key: str, stats_fn, spec,
                            labels: dict | None = None) -> None:
        """Generic scrape-time bridge: ``stats_fn()`` dict through a
        ``(stats_key, metric, extra_labels)`` spec (admission gate,
        stream manager, tenant registry)."""

        def collect():
            return samples_from_stats(stats_fn(), spec, labels)

        self.registry.register_collector(key, collect)
