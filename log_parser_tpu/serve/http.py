"""``POST /parse`` HTTP endpoint — the reference's REST contract.

Contract parity with Parse.java:41-61:

- ``POST /parse`` consumes/produces JSON;
- a null body or null ``pod`` returns 400 with exactly
  ``{"error":"Invalid PodFailureData provided"}`` (Parse.java:45-49);
- success returns the full ``AnalysisResult`` (camelCase keys, Jackson bean
  convention) with 200;
- request/response logging mirrors Parse.java:51,55-58.

Additions over the reference (SURVEY.md §5.3 — it has no health endpoints
and no REST surface for the frequency admin API that exists only
programmatically at FrequencyTrackingService.java:101-134):

- ``GET /health`` (+ ``/health/live``, ``/health/ready``);
- ``GET /frequency/stats`` — current windowed counts per pattern id;
- ``POST /frequency/reset`` and ``POST /frequency/reset/{patternId}``.

Concurrency: requests run PIPELINED — ingest and device execution of one
request overlap the host finalize of another; only the frequency-coupled
finish phase serializes, on the engine's own ``state_lock`` (shared with
the shim transports and the admin routes). The reference's concurrency
story was an unsynchronized data race on shared pattern objects
(SURVEY.md §5.2) — not a behavior to reproduce.

Overload: ``POST /parse`` admits through the engine-wide
:class:`~log_parser_tpu.serve.admission.AdmissionController` (one gate
shared with the shim transports — docs/OPS.md "Overload & degradation").
A request may carry ``X-Request-Deadline-Ms``; one that would start past
its deadline, or that finds the bounded queue full, is refused with 429 +
``Retry-After``. During drain ``/health/ready`` answers 503 and new parses
get 503.
"""

from __future__ import annotations

import json
import logging
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from log_parser_tpu import _clock as pclock
from log_parser_tpu import native
from log_parser_tpu.models.pod import PodFailureData
from log_parser_tpu.obs import SPANS
from log_parser_tpu.obs.profiler import ProfilerBusy, ProfilerUnavailable
from log_parser_tpu.runtime import faults, pressure
from log_parser_tpu.utils import xlacache
from log_parser_tpu.utils.trace import PhaseTrace, annotation
from log_parser_tpu.runtime.engine import AnalysisEngine
from log_parser_tpu.runtime.quarantine import QuarantineRejected
from log_parser_tpu.runtime.tenancy import (
    TenantError,
    TenantForwarded,
    TenantRegistry,
)
from log_parser_tpu.serve.admission import AdmissionRejected, shared_gate

log = logging.getLogger(__name__)

_INVALID = b'{"error":"Invalid PodFailureData provided"}'
# admin bodies (/patterns/reload, /frequency/restore) are operator input,
# not parse traffic — bound them so a runaway payload cannot balloon the
# process before validation even starts
_ADMIN_MAX_BODY = 4 << 20
# a migration bundle carries a whole tenant's folded state (frequency
# ages + parked candidates + session windows) — bounded by the same cap
# the frequency WAL puts on one record
_MIGRATE_MAX_BODY = 64 << 20
_TOO_LARGE = b'{"error":"payload too large"}'


class ParseServer(ThreadingHTTPServer):
    daemon_threads = True
    # socketserver's default listen backlog is 5; a synchronized burst
    # (the micro-batching client pattern) can overflow it and get
    # connection-refused before admission control ever sees the request
    request_queue_size = 128

    def __init__(
        self,
        address: tuple[str, int],
        engine: AnalysisEngine,
        tenants: TenantRegistry | None = None,
    ):
        super().__init__(address, _Handler)
        self.engine = engine
        # the engine's own state lock: admin routes and the analyze finish
        # phase serialize on ONE lock across every transport (HTTP + shim)
        self.analyze_lock = engine.state_lock
        # ... and the engine's one admission gate, shared the same way
        self.admission = shared_gate(engine)
        # tenant resolution (X-Tenant header → TenantContext). Always
        # present: without --tenant-root only the default tenant resolves
        # and non-default ids answer 404, so single-tenant deployments
        # keep their exact pre-tenancy behavior.
        self.tenants = (
            tenants
            if tenants is not None
            else TenantRegistry(engine, gate=self.admission)
        )
        # observability plane (log_parser_tpu/obs): one bundle, rooted at
        # the engine, shared by every transport and tenant engine
        self.obs = engine.obs
        # hot pattern reload (runtime/reload.py): set by serve/__main__.py
        # (or lazily on the first POST /patterns/reload); the watcher is
        # the optional --watch-patterns poller, stopped with the server
        self.reloader = None
        self.watcher = None
        # streaming follow-mode sessions (runtime/stream.py): lazily
        # created on the first POST /parse/stream; serve/__main__.py
        # flips stream_enabled off for sharded/distributed engines (the
        # session layer's residual program is the single-device cube,
        # same gate as --batching / --line-cache-mb)
        self.stream_manager = None
        self.stream_enabled = True
        self._stream_lock = threading.Lock()
        # tenant migration + drain (runtime/migrate.py): wired by
        # serve/__main__.py when --state-dir is set; None answers the
        # admin routes with 501
        self.migrator = None
        self.drain_supervisor = None
        # warm-standby replication (runtime/replicate.py): wired by
        # serve/__main__.py when --replica-target/--replica-of is set;
        # None answers /admin/replica/feed and /admin/promote with 501
        self.replicator = None

    @property
    def dropped_responses(self) -> int:
        """Responses we failed to write because the client had already
        gone away (GET /trace/last "droppedResponses") — a view over the
        registry's cross-transport drop counter, not a second tally."""
        return self.obs.dropped_responses

    def get_reloader(self):
        from log_parser_tpu.runtime.reload import PatternReloader

        if self.reloader is None:
            self.reloader = PatternReloader(self.engine)
        return self.reloader

    def get_stream_manager(self, ctx=None):
        """The stream manager for ``ctx``'s engine (default engine when
        ``ctx`` is None). ONE manager per engine across transports — a
        gRPC StreamParse session and an HTTP one share the registry, the
        admission budget, and the /trace/last counters; each tenant gets
        its own manager so sessions pin to that tenant's bank epoch."""
        if not self.stream_enabled:
            return None
        engine = self.engine if ctx is None else ctx.engine
        with self._stream_lock:
            from log_parser_tpu.runtime.stream import shared_manager

            mgr = shared_manager(engine)
            if engine is self.engine:
                self.stream_manager = mgr
            return mgr


class _Handler(BaseHTTPRequestHandler):
    server: ParseServer

    # ------------------------------------------------------------- plumbing

    def log_message(self, fmt: str, *args) -> None:  # route to logging, not stderr
        log.debug("%s " + fmt, self.address_string(), *args)

    def _send_json(
        self, status: int, payload: bytes, headers: dict[str, str] | None = None
    ) -> None:
        self._send_body(status, payload, "application/json", headers)

    def _send_body(
        self,
        status: int,
        payload: bytes,
        content_type: str,
        headers: dict[str, str] | None = None,
    ) -> None:
        try:
            self.send_response(status)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(payload)))
            for key, value in (headers or {}).items():
                self.send_header(key, value)
            self.end_headers()
            self.wfile.write(payload)
        except (BrokenPipeError, ConnectionResetError) as exc:
            # the client hung up first (its own timeout, or a shed it did
            # not wait for). Not a server fault: count it in the shared
            # cross-transport drop counter, keep the worker thread's
            # stderr free of ThreadingHTTPServer's default traceback spew.
            self.server.obs.note_dropped("http")
            log.debug(
                "client %s disconnected before the response: %s",
                self.address_string(),
                exc,
            )
            self.close_connection = True

    def _tenant(self):
        """Resolve this request's ``X-Tenant`` header to its context, or
        answer the error (400 malformed / 404 unknown / 500 on an
        injected resolve fault) and return None. Requests without the
        header run as the default tenant — the engine the server booted
        with — so pre-tenancy clients are untouched.

        The context comes back pinned (eviction-proof); the do_GET /
        do_POST wrappers unpin it when the handler returns."""
        try:
            ctx = self.server.tenants.resolve(self.headers.get("X-Tenant"))
            self._leases.append(ctx)
            return ctx
        except TenantForwarded as exc:
            # post-cutover forward (runtime/migrate.py): the tenant lives
            # elsewhere now. 307 preserves the method+body; Retry-After
            # paces callers that re-resolve through a stale balancer.
            self._send_json(
                exc.status,
                json.dumps(
                    {"error": exc.reason, "location": exc.location}
                ).encode(),
                headers={
                    "Location": exc.location,
                    "Retry-After": str(exc.retry_after_s),
                },
            )
            return None
        except TenantError as exc:
            self._send_json(
                exc.status,
                json.dumps({"error": exc.reason}).encode(),
            )
            return None
        except Exception:
            log.exception("tenant resolution failed")
            self._send_json(
                500, b'{"error":"Internal tenant resolution failure"}'
            )
            return None

    # --------------------------------------------------------------- routes

    def do_POST(self) -> None:
        self._leases: list = []
        try:
            self._route_post()
        finally:
            # the request is answered: release the tenant lease so the
            # context becomes evictable again
            for ctx in self._leases:
                ctx.unpin()

    def do_GET(self) -> None:
        self._leases = []
        try:
            self._route_get()
        finally:
            for ctx in self._leases:
                ctx.unpin()

    def _route_post(self) -> None:
        if self.path == "/parse":
            return self._parse()
        if self.path == "/parse/stream":
            return self._parse_stream()
        if self.path == "/patterns/reload":
            return self._patterns_reload()
        if self.path == "/patterns/mined":
            return self._mined_post()
        if self.path == "/debug/profile":
            return self._debug_profile()
        if self.path == "/admin/migrate":
            return self._admin_migrate()
        if self.path == "/admin/migrate/import":
            return self._admin_migrate_import()
        if self.path == "/admin/migrate/activate":
            return self._admin_migrate_activate()
        if self.path == "/admin/drain":
            return self._admin_drain()
        if self.path == "/admin/replica/feed":
            return self._admin_replica_feed()
        if self.path == "/admin/promote":
            return self._admin_promote()
        if self.path == "/admin/budget":
            return self._admin_budget()
        if self.path == "/frequency/restore":
            bad = b'{"error":"expected {patternId: [ageSeconds >= 0]}"}'
            try:
                length = int(self.headers.get("Content-Length", 0))
                if length > _ADMIN_MAX_BODY:
                    return self._send_json(413, _TOO_LARGE)
                ages = json.loads(self.rfile.read(length) if length else b"{}")
            except ValueError:
                return self._send_json(400, bad)
            # versioned envelope (the GET /frequency/snapshot shape) and
            # the legacy bare mapping both restore; the envelope's epoch
            # is informational — restore is state, not history
            if (
                isinstance(ages, dict)
                and isinstance(ages.get("ages"), dict)
                and set(ages) <= {"ages", "epoch"}
            ):
                ages = ages["ages"]
            # validate the FULL shape before touching state: restore must be
            # all-or-nothing, never partial. Negative ages are future
            # timestamps that never prune — rejected.
            if not isinstance(ages, dict) or not all(
                isinstance(v, list)
                and all(isinstance(a, (int, float)) and a >= 0 for a in v)
                for v in ages.values()
            ):
                return self._send_json(400, bad)
            ctx = self._tenant()
            if ctx is None:
                return
            eng = ctx.engine
            with eng.state_lock:
                # a journal-backed tracker writes a barrier record here: a
                # crash right after this response still recovers the
                # restored state, not the pre-restore tail
                eng.frequency.restore(ages)
            journal = eng.journal
            epoch = 0 if journal is None else journal.epoch
            return self._send_json(
                200,
                json.dumps({"status": "restored", "epoch": epoch}).encode(),
            )
        if self.path == "/frequency/reset":
            ctx = self._tenant()
            if ctx is None:
                return
            with ctx.engine.state_lock:
                ctx.engine.frequency.reset_all_frequencies()
            return self._send_json(200, b'{"status":"reset"}')
        if self.path.startswith("/frequency/reset/"):
            pattern_id = self.path[len("/frequency/reset/") :]
            ctx = self._tenant()
            if ctx is None:
                return
            with ctx.engine.state_lock:
                ctx.engine.frequency.reset_pattern_frequency(pattern_id)
            return self._send_json(200, b'{"status":"reset"}')
        self._send_json(404, b'{"error":"not found"}')

    def _patterns_reload(self) -> None:
        """Canary-gated hot reload (runtime/reload.py). Empty body: re-read
        the configured pattern directory. Non-empty body: inline YAML
        pattern sets. Any build/canary failure is a structured 409 and the
        live engine is untouched — in-flight requests never notice.

        Tenant-scoped: ``X-Tenant`` picks whose library swaps. The quiesce
        runs on that tenant's engine alone, so every other tenant's
        traffic proceeds uninterrupted through the whole ladder."""
        from log_parser_tpu.runtime.reload import ReloadError

        try:
            length = int(self.headers.get("Content-Length", 0))
            if length > _ADMIN_MAX_BODY:
                return self._send_json(413, _TOO_LARGE)
            body = self.rfile.read(length) if length else b""
        except ValueError:
            return self._send_json(400, b'{"error":"bad request body"}')
        try:
            yaml_text = body.decode("utf-8") if body.strip() else None
        except UnicodeDecodeError:
            return self._send_json(400, b'{"error":"body is not UTF-8"}')
        ctx = self._tenant()
        if ctx is None:
            return
        default = ctx.engine is self.server.engine
        reloader = self.server.get_reloader() if default else ctx.reloader()
        try:
            envelope = reloader.reload(yaml_text=yaml_text)
        except ReloadError as exc:
            return self._send_json(409, json.dumps(exc.to_json()).encode())
        except Exception:
            log.exception("pattern reload failed")
            return self._send_json(
                500, b'{"error":"Internal reload failure"}'
            )
        ctx.note_reloaded()
        return self._send_json(200, json.dumps(envelope).encode())

    def _mined_get(self) -> None:
        """``GET /patterns/mined``: the review queue — parked candidates
        (id, template, support, tier; the YAML itself stays on disk) plus
        the miner's live counters. Tenant-scoped: ``X-Tenant`` picks whose
        miner answers; 404 when mining is off for that engine."""
        ctx = self._tenant()
        if ctx is None:
            return
        miner = getattr(ctx.engine, "miner", None)
        if miner is None:
            return self._send_json(404, b'{"error":"miner disabled"}')
        return self._send_json(
            200,
            json.dumps(
                {"pending": miner.pending_list(), "stats": miner.stats()}
            ).encode(),
        )

    def _mined_post(self) -> None:
        """``POST /patterns/mined`` with ``{"id": ..., "action":
        "approve"|"reject"}``. Approve re-runs the FULL admission ladder
        (the curated library may have changed since parking) — a gate
        failure is a structured 409 carrying the rejection reason, and the
        candidate stays parked for triage. Reject discards the parked
        candidate."""
        from log_parser_tpu.mining.admit import Rejection

        bad = b'{"error":"expected {id, action: approve|reject}"}'
        try:
            length = int(self.headers.get("Content-Length", 0))
            if length > _ADMIN_MAX_BODY:
                return self._send_json(413, _TOO_LARGE)
            body = json.loads(self.rfile.read(length) if length else b"{}")
        except ValueError:
            return self._send_json(400, bad)
        if (
            not isinstance(body, dict)
            or not isinstance(body.get("id"), str)
            or body.get("action") not in ("approve", "reject")
        ):
            return self._send_json(400, bad)
        ctx = self._tenant()
        if ctx is None:
            return
        miner = getattr(ctx.engine, "miner", None)
        if miner is None:
            return self._send_json(404, b'{"error":"miner disabled"}')
        if body["action"] == "reject":
            found = miner.discard(body["id"])
            if not found:
                return self._send_json(404, b'{"error":"unknown candidate"}')
            return self._send_json(200, b'{"status":"rejected"}')
        try:
            result = miner.approve(body["id"])
        except KeyError:
            return self._send_json(404, b'{"error":"unknown candidate"}')
        except Rejection as exc:
            return self._send_json(409, json.dumps(exc.to_json()).encode())
        except Exception:
            log.exception("mined-candidate approval failed")
            return self._send_json(
                500, b'{"error":"Internal approval failure"}'
            )
        return self._send_json(200, json.dumps(result).encode())

    # ---------------------------------------------------- migration admin

    def _admin_body(self, max_body: int = _ADMIN_MAX_BODY):
        """Parsed JSON object body for an admin route, or None after
        answering the error."""
        try:
            length = int(self.headers.get("Content-Length", 0))
            if length > max_body:
                self._send_json(413, _TOO_LARGE)
                return None
            body = json.loads(self.rfile.read(length) if length else b"{}")
        except ValueError:
            self._send_json(400, b'{"error":"bad request body"}')
            return None
        if not isinstance(body, dict):
            self._send_json(400, b'{"error":"expected a JSON object"}')
            return None
        return body

    def _admin_budget(self) -> None:
        """``POST /admin/budget`` ``{"lineCacheMb": x, "tenantBudgetMb":
        y}``: apply a fleet-arbitrated budget share live — the router's
        arbiter (fleet/budget.py) replaces the process-local
        ``--line-cache-mb`` / ``--tenant-budget-mb`` constants with
        these pushes. Shrinking evicts down immediately."""
        body = self._admin_body()
        if body is None:
            return
        line_mb = body.get("lineCacheMb")
        tenant_mb = body.get("tenantBudgetMb")
        if line_mb is None and tenant_mb is None:
            return self._send_json(
                400,
                b'{"error":"expected {lineCacheMb and/or tenantBudgetMb}"}',
            )
        applied = {}
        try:
            if line_mb is not None:
                line_mb = max(0.0, float(line_mb))
                self.server.tenants.set_line_cache_budget(
                    int(line_mb * 1024 * 1024)
                )
                applied["lineCacheMb"] = line_mb
            if tenant_mb is not None:
                tenant_mb = max(0.0, float(tenant_mb))
                self.server.tenants.set_budget_mb(tenant_mb)
                applied["tenantBudgetMb"] = tenant_mb
        except (TypeError, ValueError):
            return self._send_json(
                400, b'{"error":"budgets must be numbers"}'
            )
        return self._send_json(200, json.dumps(applied).encode())

    def _require_migrator(self):
        mig = self.server.migrator
        if mig is None:
            self._send_json(
                501,
                b'{"error":"migration is not enabled (serve with '
                b'--state-dir)"}',
            )
        return mig

    def _require_replication(self):
        rep = self.server.replicator
        if rep is None:
            self._send_json(
                501,
                b'{"error":"replication is not enabled (serve with '
                b'--state-dir and --replica-target/--replica-of)"}',
            )
        return rep

    def _admin_replica_feed(self) -> None:
        """``POST /admin/replica/feed``: one shipped WAL batch from the
        primary — a snapshot barrier, or base64 CRC-framed records at
        the tenant's acked offset. Verified and applied whole, or
        refused with the receiver's position so the sender re-syncs;
        a refused batch never moves the acked offset."""
        from log_parser_tpu.runtime.replicate import ReplicationError

        rep = self._require_replication()
        if rep is None:
            return
        body = self._admin_body(max_body=_MIGRATE_MAX_BODY)
        if body is None:
            return
        try:
            ack = rep.feed(body)
        except ReplicationError as exc:
            return self._send_json(
                exc.status if exc.status else 503,
                json.dumps(exc.to_json()).encode(),
            )
        except Exception:
            log.exception("replica feed failed")
            return self._send_json(
                500, b'{"error":"Internal replication failure"}'
            )
        return self._send_json(200, json.dumps(ack).encode())

    def _admin_promote(self) -> None:
        """``POST /admin/promote`` ``{["reason": text]}``: manual
        failover — journal PROMOTE(epoch+1), activate every replicated
        tenant, lift the fence. Idempotent on an already-primary
        process; the abandoned primary demotes itself the moment it
        sees the higher epoch."""
        from log_parser_tpu.runtime.replicate import ReplicationError

        rep = self._require_replication()
        if rep is None:
            return
        body = self._admin_body()
        if body is None:
            return
        reason = body.get("reason")
        try:
            summary = rep.promote(
                reason=str(reason) if isinstance(reason, str) and reason
                else "admin"
            )
        except ReplicationError as exc:
            return self._send_json(
                exc.status if exc.status else 503,
                json.dumps(exc.to_json()).encode(),
            )
        except Exception:
            log.exception("promotion failed")
            return self._send_json(
                500, b'{"error":"Internal replication failure"}'
            )
        return self._send_json(200, json.dumps(summary).encode())

    def _admin_migrate(self) -> None:
        """``POST /admin/migrate`` ``{"tenant": id, "target": url[,
        "retryAfterS": n]}``: run the full source side of the migration
        protocol against the target process's import endpoints. Blocks
        until CUTOVER+COMPLETE (or a pre-cutover abort, answered as a
        structured 4xx/5xx with the tenant still owned here)."""
        from log_parser_tpu.runtime.migrate import HttpTarget, MigrationError

        mig = self._require_migrator()
        if mig is None:
            return
        body = self._admin_body()
        if body is None:
            return
        tenant = body.get("tenant")
        target = body.get("target")
        if not isinstance(tenant, str) or not isinstance(target, str):
            return self._send_json(
                400, b'{"error":"expected {tenant, target}"}'
            )
        try:
            retry_after = int(body.get("retryAfterS", 5))
        except (TypeError, ValueError):
            return self._send_json(400, b'{"error":"bad retryAfterS"}')
        try:
            summary = mig.migrate(
                tenant, HttpTarget(target), retry_after_s=retry_after
            )
        except MigrationError as exc:
            return self._send_json(
                exc.status, json.dumps({"error": exc.reason}).encode()
            )
        except Exception:
            log.exception("migration of %r failed", tenant)
            return self._send_json(
                500, b'{"error":"Internal migration failure"}'
            )
        return self._send_json(200, json.dumps(summary).encode())

    def _admin_migrate_import(self) -> None:
        """``POST /admin/migrate/import`` ``{"bundle": {...}, "sha":
        hex}``: the target half's STAGE step — verify + warm-build +
        persist, ack with the sha. Nothing is applied until activate."""
        from log_parser_tpu.runtime.migrate import MigrationError

        mig = self._require_migrator()
        if mig is None:
            return
        body = self._admin_body(max_body=_MIGRATE_MAX_BODY)
        if body is None:
            return
        bundle = body.get("bundle")
        sha = body.get("sha")
        if not isinstance(bundle, dict) or not isinstance(sha, str):
            return self._send_json(
                400, b'{"error":"expected {bundle, sha}"}'
            )
        try:
            ack = mig.stage_import(bundle, sha)
        except MigrationError as exc:
            return self._send_json(
                exc.status, json.dumps({"error": exc.reason}).encode()
            )
        except Exception:
            log.exception("migration import failed")
            return self._send_json(
                500, b'{"error":"Internal import failure"}'
            )
        return self._send_json(200, json.dumps(ack).encode())

    def _admin_migrate_activate(self) -> None:
        """``POST /admin/migrate/activate`` ``{"mid": id}``: apply a
        staged import (the source's CUTOVER is durable by the time it
        calls this)."""
        from log_parser_tpu.runtime.migrate import MigrationError

        mig = self._require_migrator()
        if mig is None:
            return
        body = self._admin_body()
        if body is None:
            return
        mid = body.get("mid")
        if not isinstance(mid, str) or not mid:
            return self._send_json(400, b'{"error":"expected {mid}"}')
        try:
            summary = mig.activate(mid)
        except MigrationError as exc:
            return self._send_json(
                exc.status, json.dumps({"error": exc.reason}).encode()
            )
        except Exception:
            log.exception("migration activate failed")
            return self._send_json(
                500, b'{"error":"Internal activate failure"}'
            )
        return self._send_json(200, json.dumps(summary).encode())

    def _admin_drain(self) -> None:
        """``POST /admin/drain``: run one drain-supervisor pass — flip
        admission (readiness 503), migrate every resident tenant to the
        configured ``--drain-target`` under ``--drain-deadline-s``
        (bounded local close when there is no target), finalize every
        engine. Blocks until the pass completes and returns its summary;
        the process keeps running (SIGTERM drains AND exits)."""
        sup = self.server.drain_supervisor
        if sup is None:
            return self._send_json(
                501, b'{"error":"drain supervisor is not enabled"}'
            )
        try:
            summary = sup.drain(reason="admin")
        except Exception:
            log.exception("drain failed")
            return self._send_json(500, b'{"error":"Internal drain failure"}')
        return self._send_json(200, json.dumps(summary).encode())

    def _route_get(self) -> None:
        if self.path in ("/health", "/health/live", "/health/ready", "/q/health"):
            # draining: readiness fails (load balancers stop sending) but
            # liveness holds — in-flight work is still finishing
            if self.path == "/health/ready" and self.server.admission.draining:
                return self._send_json(
                    503,
                    b'{"status":"DOWN","checks":[{"name":"draining",'
                    b'"status":"DOWN"}]}',
                )
            # still UP while degraded — requests serve from the host path
            # (circuit open) or the coordinator's local devices (follower
            # group dead) — but the degradation is visible to probes
            checks = []
            sup = self.server.drain_supervisor
            if (sup is not None and sup.draining) or (
                self.server.admission.draining
            ):
                # the drain supervisor is evacuating this process: the
                # aggregated probe reports a DRAINING check, and answers
                # ready-503 so load balancers stop routing here while
                # in-flight migrations finish. Liveness (/health,
                # /health/live) holds throughout — killing a draining
                # process forfeits the handoff.
                checks.append({"name": "drain", "status": "DRAINING"})
                if self.path == "/q/health":
                    return self._send_json(
                        503,
                        json.dumps(
                            {"status": "DRAINING", "checks": checks}
                        ).encode(),
                    )
            if self.server.engine.watchdog.circuit_open:
                checks.append({"name": "device", "status": "DEGRADED"})
            mesh = getattr(self.server.engine, "mesh_health", None)
            if mesh is not None and mesh.degraded:
                checks.append({"name": "mesh", "status": "DEGRADED"})
            journal = self.server.engine.journal
            if journal is not None and not journal.healthy:
                # requests still serve, but frequency durability is gone:
                # a crash now loses the un-journaled tail
                checks.append({"name": "journal", "status": "DEGRADED"})
            if self.server.engine.breakers.any_active():
                # shadow verification caught a device-vs-golden divergence:
                # the divergent pattern(s) serve from the host regex until
                # a clean half-open probe (docs/OPS.md "Shadow divergence")
                checks.append({"name": "shadow", "status": "DEGRADED"})
            rep = self.server.replicator
            if rep is not None and rep.role == "standby":
                # informational, not DOWN: a standby is healthy but fenced
                # — client traffic 307s to the owner while feeds apply.
                # The failover supervisor on the OTHER side probes this
                # same endpoint, which must stay 200 while we are alive.
                checks.append({
                    "name": "replication", "status": "STANDBY",
                    "epoch": rep.epoch,
                })
            ctl = pressure.current()
            if ctl is not None:
                pc = ctl.health_check()
                if pc["status"] != "UP":
                    # resource pressure (disk/memory ladder off ``ok``):
                    # still a 200 — the ladder's whole contract is that
                    # the serving path keeps answering while degraded
                    # (docs/OPS.md "Resource exhaustion")
                    checks.append(pc)
            slo = self.server.obs.slo.health()
            if slo is not None and slo["status"] != "UP":
                # SLO burn: an objective is spending its error budget
                # faster than the threshold on every configured window
                # (docs/OPS.md "Observability" — SLO runbook)
                checks.append(slo)
            if checks:
                return self._send_json(
                    200, json.dumps({"status": "UP", "checks": checks}).encode()
                )
            return self._send_json(200, b'{"status":"UP"}')
        if self.path == "/frequency/stats":
            ctx = self._tenant()
            if ctx is None:
                return
            with ctx.engine.state_lock:
                stats = ctx.engine.frequency.get_frequency_statistics()
            return self._send_json(200, json.dumps(stats).encode())
        if self.path == "/frequency/snapshot":
            ctx = self._tenant()
            if ctx is None:
                return
            with ctx.engine.state_lock:
                snap = ctx.engine.frequency.snapshot()
            journal = ctx.engine.journal
            epoch = 0 if journal is None else journal.epoch
            # versioned envelope; POST /frequency/restore accepts it as-is
            return self._send_json(
                200, json.dumps({"epoch": epoch, "ages": snap}).encode()
            )
        if self.path == "/patterns/mined":
            return self._mined_get()
        if self.path == "/trace/last":
            trace = self.server.engine.last_trace
            payload = {"phasesMs": {}, "totalMs": 0.0} if trace is None else {
                "phasesMs": {k: v * 1e3 for k, v in trace.as_dict().items()},
                "totalMs": trace.total * 1e3,
            }
            payload["fallbackCount"] = self.server.engine.fallback_count
            payload["hostRoutedCount"] = self.server.engine.host_routed_count
            payload["deviceCircuitOpen"] = (
                self.server.engine.watchdog.circuit_open
            )
            # a view over the registry's cross-transport drop counter
            payload["droppedResponses"] = self.server.dropped_responses
            payload["admission"] = self.server.admission.stats()
            # trace-ring occupancy (GET /trace/recent reads the entries)
            payload["traceRing"] = self.server.obs.ring.stats()
            # causal span store occupancy (GET /trace/spans reads the
            # trees; docs/OPS.md "Span tracing & utilization accounting")
            payload["spans"] = self.server.obs.spans.stats()
            batcher = getattr(self.server.engine, "batcher", None)
            if batcher is not None:
                # queue depth, batch sizes, flush reasons (docs/OPS.md
                # "Micro-batching")
                payload["batcher"] = batcher.stats()
            line_cache = getattr(self.server.engine, "line_cache", None)
            if line_cache is not None:
                # routing-tier hit/residual/eviction counters (docs/OPS.md
                # "Line cache (routing tier)")
                payload["lineCache"] = line_cache.stats()
            kernel_stats = getattr(self.server.engine, "kernel_stats", None)
            if kernel_stats is not None:
                # Pallas union-DFA kernel tier: admission reason +
                # per-dispatch routing counters (docs/OPS.md "Kernel tier")
                payload["kernel"] = kernel_stats.stats()
            mesh = getattr(self.server.engine, "mesh_health", None)
            if mesh is not None:
                # follower liveness + degrade-to-local counters
                # (docs/OPS.md "Distributed failure modes")
                payload["distributed"] = mesh.stats()
            journal = self.server.engine.journal
            if journal is not None:
                # WAL/snapshot counters (docs/OPS.md "State durability")
                payload["journal"] = journal.stats()
            stream_mgr = self.server.stream_manager
            if stream_mgr is not None:
                # follow-mode session counters (docs/OPS.md "Streaming
                # follow-mode")
                payload["stream"] = stream_mgr.stats()
            # which ingest path this process runs, and why the native
            # scanner refused to load when it did (docs/OPS.md "Which
            # ingest am I running?")
            payload["native"] = native.stats()
            # persistent XLA compile cache wiring + hit/miss tally
            # (docs/OPS.md "Compile cache")
            payload["compileCache"] = xlacache.stats()
            # poison-request ledger (docs/OPS.md "Poison-request triage")
            payload["quarantine"] = self.server.engine.quarantine.stats()
            miner = getattr(self.server.engine, "miner", None)
            if miner is not None:
                # template-miner loop: tap/cluster/admission counters
                # (docs/OPS.md "Template miner")
                payload["miner"] = miner.stats()
            shadow = getattr(self.server.engine, "shadow", None)
            if shadow is not None:
                # online device-vs-golden verification + per-pattern
                # breakers (docs/OPS.md "Shadow divergence")
                payload["shadow"] = shadow.stats()
            payload["reload"] = {
                "epoch": self.server.engine.reload_epoch,
                "count": self.server.engine.reload_count,
                "failures": self.server.engine.reload_failures,
                "lastError": self.server.engine.last_reload_error,
            }
            last_lint = getattr(self.server.engine, "last_lint", None)
            if last_lint is not None:
                # static-analysis summary of the most recent reload
                # candidate (docs/OPS.md "Lint-blocked reload")
                payload["lint"] = last_lint
            # tenant residency/quota counters (docs/OPS.md "Multi-tenant
            # serving")
            payload["tenants"] = self.server.tenants.stats()
            migrator = self.server.migrator
            if migrator is not None:
                # migration protocol + drain counters (docs/OPS.md
                # "Tenant migration & drain")
                mig_stats = migrator.stats()
                sup = self.server.drain_supervisor
                if sup is not None:
                    mig_stats["drain"] = sup.stats()
                payload["migration"] = mig_stats
            replicator = self.server.replicator
            if replicator is not None:
                # replication channel + failover position (docs/OPS.md
                # "Warm-standby replication")
                payload["replication"] = replicator.stats()
            ctl = pressure.current()
            if ctl is not None:
                # resource-pressure ladder, levers and retry budget
                # (docs/OPS.md "Resource exhaustion")
                payload["pressure"] = ctl.stats()
            fault_stats = faults.stats()
            if fault_stats is not None:
                payload["faults"] = fault_stats
            return self._send_json(200, json.dumps(payload).encode())
        if self.path == "/metrics":
            # Prometheus text exposition: owned hot-path instruments plus
            # scrape-time collectors over every subsystem's stats() — the
            # same variables /trace/last reads (docs/OPS.md
            # "Observability")
            return self._send_body(
                200,
                self.server.obs.registry.render().encode(),
                "text/plain; version=0.0.4; charset=utf-8",
            )
        if self.path.startswith("/trace/recent"):
            query = urllib.parse.urlparse(self.path).query
            params = urllib.parse.parse_qs(query)
            try:
                n = int(params.get("n", ["50"])[0])
            except ValueError:
                return self._send_json(400, b'{"error":"n must be an integer"}')
            ring = self.server.obs.ring
            return self._send_json(200, json.dumps({
                "requests": ring.recent(n),
                "slow": ring.slow_recent(n),
                "ring": ring.stats(),
            }).encode())
        if self.path.startswith("/trace/spans"):
            # self-contained causal trees: request -> flush(link) ->
            # dispatch -> finalize, plus session/tenancy lifecycles
            # (docs/OPS.md "Span tracing & utilization accounting")
            query = urllib.parse.urlparse(self.path).query
            params = urllib.parse.parse_qs(query)
            try:
                n = int(params.get("n", ["50"])[0])
            except ValueError:
                return self._send_json(400, b'{"error":"n must be an integer"}')
            spans = self.server.obs.spans
            return self._send_json(200, json.dumps({
                "traces": spans.traces(n),
                "store": spans.stats(),
                "vocabulary": sorted(SPANS),
            }).encode())
        if self.path == "/debug/factors":
            fin = self.server.engine.last_finalized
            rows = [] if fin is None else fin.factor_rows(self.server.engine.bank)
            return self._send_json(200, json.dumps(rows).encode())
        self._send_json(404, b'{"error":"not found"}')

    def _parse_stream(self) -> None:
        """``POST /parse/stream``: chunked follow-mode ingestion. Each HTTP
        request chunk (``Transfer-Encoding: chunked``, hand-decoded — the
        stdlib handler never decodes request bodies) is one session chunk;
        the response is NDJSON frames (``emit`` / ``revised`` / ``final`` /
        ``error``, runtime/stream.py FRAME_TYPES) written full-duplex as
        chunks arrive, so time-to-first-detection is one chunk deep, not
        one blob deep. The zero-size chunk closes the session; the final
        frame's result is bit-identical to one-shot ``POST /parse`` on the
        concatenated body. A fixed-length body is treated as a single
        chunk + close."""
        try:
            faults.fire("http")
        except Exception:
            log.exception("injected HTTP-transport fault")
            return self._send_json(500, b'{"error":"Internal analysis failure"}')
        ctx = self._tenant()
        if ctx is None:
            return
        mgr = self.server.get_stream_manager(ctx)
        if mgr is None:
            return self._send_json(
                501, b'{"error":"streaming is not supported on this engine"}'
            )
        deadline_ms = None
        header = self.headers.get("X-Request-Deadline-Ms")
        if header is not None:
            try:
                deadline_ms = float(header)
            except ValueError:
                return self._send_json(
                    400, b'{"error":"invalid X-Request-Deadline-Ms"}'
                )
        try:
            sess = mgr.open(deadline_ms)
        except AdmissionRejected as exc:
            return self._send_json(
                exc.status,
                json.dumps({"error": "overloaded", "reason": exc.reason}).encode(),
                headers={"Retry-After": str(exc.retry_after_s)},
            )

        def _write(frames: list[dict]) -> None:
            for frame in frames:
                self.wfile.write(json.dumps(frame).encode() + b"\n")
            self.wfile.flush()

        chunked = "chunked" in (
            self.headers.get("Transfer-Encoding") or ""
        ).lower()
        try:
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Connection", "close")
            self.end_headers()
            if chunked:
                while not sess.closed:
                    size_line = self.rfile.readline(130)
                    try:
                        size = int(size_line.split(b";")[0].strip() or b"x", 16)
                    except ValueError:
                        # garbage framing: a structured error frame, never
                        # a wedged session or a half-open connection
                        _write(
                            [
                                {
                                    "type": "error",
                                    "session": sess.session_id,
                                    "reason": "bad-frame",
                                    "message": "malformed chunk size line",
                                }
                            ]
                        )
                        sess.kill("bad-frame")
                        break
                    if size == 0:
                        while self.rfile.readline(130).strip():
                            pass  # discard trailers
                        _write(sess.close())
                        break
                    data = self.rfile.read(size)
                    self.rfile.read(2)  # chunk CRLF
                    _write(sess.feed(data))
            else:
                length = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(length) if length else b""
                _write(sess.feed(body))
                if not sess.closed:
                    _write(sess.close())
        except (BrokenPipeError, ConnectionResetError) as exc:
            self.server.obs.note_dropped("http")
            log.debug(
                "stream client %s disconnected: %s", self.address_string(), exc
            )
        except Exception:
            log.exception("stream session %s failed", sess.session_id)
        finally:
            if not sess.closed:
                sess.kill("transport")
            self.close_connection = True

    def _debug_profile(self) -> None:
        # on-demand jax.profiler capture: {"seconds": N} -> 202 with the
        # capture directory; single-flight, so a concurrent start is a 409
        try:
            length = int(self.headers.get("Content-Length", 0))
            if length > _ADMIN_MAX_BODY:
                return self._send_json(413, _TOO_LARGE)
            payload = json.loads(self.rfile.read(length) if length else b"{}")
            seconds = float(payload.get("seconds", 5)) if isinstance(
                payload, dict
            ) else None
        except (ValueError, TypeError):
            seconds = None
        if seconds is None:
            return self._send_json(
                400, b'{"error":"expected {\\"seconds\\": N}"}'
            )
        try:
            capture_dir = self.server.obs.profiler.start(seconds)
        except ProfilerBusy as exc:
            return self._send_json(
                409, json.dumps({"error": str(exc)}).encode()
            )
        except ProfilerUnavailable as exc:
            return self._send_json(
                503, json.dumps({"error": str(exc)}).encode()
            )
        except ValueError as exc:
            return self._send_json(
                400, json.dumps({"error": str(exc)}).encode()
            )
        return self._send_json(
            202,
            json.dumps(
                {"status": "capturing", "seconds": seconds, "dir": capture_dir}
            ).encode(),
        )

    def _parse(self) -> None:
        obs = self.server.obs
        # honor a caller-supplied correlation id, mint one otherwise; the
        # same id is echoed back and threaded through admission -> batcher
        # flush -> device dispatch so /trace/recent can stitch the hops
        rid = obs.clean_request_id(self.headers.get("X-Request-Id"))
        if rid is None:
            rid = obs.new_request_id()
        started = pclock.mono()
        cpu_started = time.thread_time()
        tenant = "default"
        route = "device"
        # the transport's own stages; observed here once the response is
        # written, since the engine's note_served runs before the encode
        stages = PhaseTrace()

        def reply(status, body, *, detail=None, headers=None):
            hdrs = dict(headers) if headers else {}
            hdrs["X-Request-Id"] = rid
            obs.note_request(
                "http",
                route,
                status,
                tenant,
                pclock.mono() - started,
                request_id=rid,
                detail=detail,
            )
            with stages.stage("transport.write"):
                self._send_json(status, body, headers=hdrs)
            obs.note_stages(stages.stage_dict(), tenant)
            obs.note_request_cpu(
                time.thread_time() - cpu_started, tenant, route
            )

        try:
            faults.fire("http")
        except Exception:
            log.exception("injected HTTP-transport fault")
            return reply(
                500, b'{"error":"Internal analysis failure"}', detail="fault"
            )
        try:
            length = int(self.headers.get("Content-Length", 0))
            with stages.stage("transport.read"):
                body = self.rfile.read(length) if length else b""
            with stages.stage("transport.decode"):
                payload = json.loads(body) if body else None
        except (ValueError, json.JSONDecodeError):
            return reply(400, _INVALID, detail="invalid body")

        with stages.stage("transport.decode"):
            data = (
                PodFailureData.from_dict(payload)
                if isinstance(payload, dict) else None
            )
            n_lines = (
                data.logs.count("\n") + 1
                if data is not None and data.logs else 0
            )
        # Parse.java:45-49 — null data or null pod is a 400
        if data is None or data.pod is None:
            return reply(400, _INVALID, detail="invalid body")

        deadline_ms = None  # None -> the gate's configured default
        header = self.headers.get("X-Request-Deadline-Ms")
        if header is not None:
            try:
                deadline_ms = float(header)
            except ValueError:
                return reply(
                    400,
                    b'{"error":"invalid X-Request-Deadline-Ms"}',
                    detail="invalid deadline",
                )

        ctx = self._tenant()
        if ctx is None:
            return
        tenant = ctx.tenant_id
        engine = ctx.engine
        batcher = getattr(engine, "batcher", None)
        arrival = pclock.mono()
        try:
            with annotation("transport.admission"):
                route = self.server.admission.acquire(
                    deadline_ms,
                    batchable=batcher is not None,
                    tenant=ctx.quota,
                    lines=n_lines,
                )
        except AdmissionRejected as exc:
            admission_s = pclock.mono() - arrival
            stages.add_stage("transport.admission", admission_s)
            # shed (429) or draining (503): tell the client when it is
            # worth coming back. A futile shed (413 `tenant burst` — the
            # request exceeds the bucket's whole capacity) carries NO
            # Retry-After: the same request can never be admitted.
            # the staged admission child attaches when reply()'s
            # note_request commits this shed request's trace
            obs.spans.annotate(
                rid, "admission", admission_s,
                attrs={"verdict": exc.reason, "tenant": tenant},
            )
            route = "admission"
            return reply(
                exc.status,
                json.dumps({"error": "overloaded", "reason": exc.reason}).encode(),
                detail=exc.reason,
                headers=(
                    {"Retry-After": str(exc.retry_after_s)}
                    if exc.retry_after_s > 0
                    else None
                ),
            )
        admission_s = pclock.mono() - arrival
        stages.add_stage("transport.admission", admission_s)
        obs.spans.annotate(
            rid, "admission", admission_s,
            attrs={"verdict": route, "tenant": tenant},
        )
        try:
            log.info("Received analysis request for pod: %s", data.pod_name)
            try:
                if route == "host":
                    # ladder rung 2: device slots saturated, this request
                    # queued — serve it from the cheaper golden host path
                    result = engine.analyze_host_routed(data, request_id=rid)
                elif batcher is not None:
                    # micro-batching on: this request ("device" or
                    # queued-then-"batched") coalesces with concurrent
                    # arrivals into one shared device batch. Pass the
                    # REMAINING deadline budget — time already burned
                    # waiting for admission must pull the flush earlier.
                    route = "batched"  # the metrics label matches the ring
                    effective = (
                        deadline_ms
                        if deadline_ms is not None
                        else (self.server.admission.default_deadline_ms or None)
                    )
                    if effective is not None:
                        effective -= (pclock.mono() - arrival) * 1e3
                    result = engine.analyze_batched(
                        data, effective, request_id=rid
                    )
                else:
                    # pipelined: ingest + device work of this request
                    # overlaps the host finalize of in-flight ones; only
                    # the frequency-coupled finish phase serializes (on
                    # engine.state_lock)
                    result = engine.analyze_pipelined(data, request_id=rid)
            except QuarantineRejected as exc:
                # a quarantined fingerprint the golden host path could not
                # serve either — structured 429, try again after the TTL
                return reply(
                    exc.status,
                    json.dumps(
                        {
                            "error": "quarantined",
                            "reason": exc.reason,
                            "fingerprint": exc.fingerprint,
                        }
                    ).encode(),
                    detail="quarantined",
                    headers={"Retry-After": str(exc.retry_after_s)},
                )
            except Exception:
                # non-device bugs propagate out of analyze() by design
                # (runtime/engine.py is_device_error) — answer with a JSON
                # 500 instead of dropping the connection mid-request
                log.exception("Analysis failed for pod: %s", data.pod_name)
                return reply(
                    500, b'{"error":"Internal analysis failure"}', detail="error"
                )
        finally:
            self.server.admission.release(tenant=ctx.quota)
        log.info(
            "Analysis complete for pod: %s. Found %d significant events.",
            data.pod_name,
            result.summary.significant_events if result.summary else 0,
        )
        # pressure.stamp marks the envelope ``durability: degraded``
        # while the disk ladder is hard — its absence is a promise that
        # this response's frequency updates ride an fsync'd journal
        with stages.stage("transport.encode"):
            answer = json.dumps(
                pressure.stamp(result.to_dict(drop_none=True))
            ).encode()
        reply(200, answer)


def make_server(
    engine: AnalysisEngine,
    host: str = "0.0.0.0",
    port: int = 8080,
    tenants: TenantRegistry | None = None,
) -> ParseServer:
    return ParseServer((host, port), engine, tenants=tenants)
