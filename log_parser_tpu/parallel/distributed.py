"""Multi-process (DCN) scale-out: one `jax.sharding.Mesh` spanning
processes, coordinated by `jax.distributed` (SURVEY.md §2.2/§5.8).

The reference is a single JVM with no inter-process communication at all;
its only network surface is HTTP :8080 (Dockerfile.native:28). The
TPU-native equivalent of "scale beyond one host" is NOT a message bus but
a bigger mesh: `jax.distributed.initialize` connects N processes (each
owning its local chips) into one runtime, `jax.devices()` becomes the
global device list, and the existing `shard_map` program from
parallel/sharded.py runs unchanged — XLA routes `ppermute`/`all_gather`
over ICI within a host and DCN between hosts.

Serving model: process 0 (the coordinator) owns the HTTP/gRPC surface.
Every process must participate in every SPMD dispatch, so the coordinator
broadcasts each request's raw payload to the followers
(`broadcast_one_to_all` rides the same distributed runtime), and every
process runs the identical analyze() pipeline in lockstep. Followers
discard their (identical) results; the coordinator answers the client.

Resilience (parallel/resilience.py): every coordinator→follower dispatch
runs under a deadline and is retried with backoff while it provably never
entered a collective; a group that stops acking is declared dead and the
coordinator flips to **degrade-to-local** — requests run on its local
devices through a private single-process `ShardedFusedStep` (or the
golden host path when it has none), stamped ``metadata.degraded =
"distributed-fallback"``. A background heartbeat (`_PING` broadcast +
ack `process_allgather`) keeps per-follower liveness fresh and re-admits
the mesh once followers respond again. The control-plane collectives are
behind a swappable :class:`Transport` so single-process tests can drive
the whole ladder with a stub follower group.

Frequency note: each process evolves its own host-side frequency tracker
from the same deterministic request stream, so trackers agree except for
sub-second wall-clock skew at window boundaries. Device dispatches take no
frequency input (finalization is host-side, runtime/finalize.py), so skew
can never desynchronize the collectives; the coordinator's scores are the
canonical response. Admin mutations (reset/restore) apply on the
coordinator only — snapshot/restore across a restart re-seeds followers.
During a degraded window only the coordinator advances its tracker; on
readmission followers resume from their pre-window state, which widens
the same benign skew and keeps the coordinator canonical.
"""

from __future__ import annotations

import json
import logging
import os
import threading

import numpy as np

from log_parser_tpu.models.pod import PodFailureData
from log_parser_tpu.parallel.resilience import (
    DEGRADED_MARKER,
    ENV_HEARTBEAT_S,
    MeshHealth,
    MeshUnavailable,
    RetryPolicy,
    dispatch_with_retry,
)
from log_parser_tpu.parallel.sharded import ShardedEngine, ShardedFusedStep
from log_parser_tpu.runtime import faults
from log_parser_tpu.utils.trace import NO_TRACE

log = logging.getLogger(__name__)

_SHUTDOWN = b"\x00shutdown"
_PING = b"\x00ping"
# reload-epoch broadcast: sentinel prefix + JSON {"epoch": N, "sets": [...]}
# — followers rebuild the library and swap in lockstep (runtime/reload.py)
_RELOAD = b"\x00reload:"


def init_distributed(
    coordinator: str,
    num_processes: int,
    process_id: int,
    initialization_timeout: int = 120,
) -> None:
    """Join this process into the distributed runtime. After this call
    `jax.devices()` is the GLOBAL device list across all processes and
    `make_mesh()` builds a mesh spanning them."""
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
        initialization_timeout=initialization_timeout,
    )
    log.info(
        "distributed runtime up: process %d/%d, %d local + %d global devices",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
    )


class JaxProcessTransport:
    """The real control plane: byte broadcast + ack allgather as collectives
    over the `jax.distributed` runtime."""

    def process_count(self) -> int:
        import jax

        return jax.process_count()

    def process_index(self) -> int:
        import jax

        return jax.process_index()

    def broadcast(self, payload: bytes | None) -> bytes:
        """Broadcast a byte string from process 0 to every process (two
        fixed-shape collectives: an int64 length header, then the buffer).
        Non-coordinators pass ``None`` and receive the coordinator's
        bytes."""
        from jax.experimental import multihost_utils as mh

        header = np.array(
            [len(payload) if payload is not None else 0], dtype=np.int64
        )
        n = int(np.asarray(mh.broadcast_one_to_all(header))[0])
        if n == 0:
            return b""
        buf = (
            np.frombuffer(payload, dtype=np.uint8)
            if payload is not None
            else np.zeros((n,), dtype=np.uint8)
        )
        out = np.asarray(mh.broadcast_one_to_all(buf))
        return out.tobytes()

    def allgather(self, row: np.ndarray) -> np.ndarray:
        """Every process contributes one fixed-shape row; all receive the
        [P, ...] stack — the heartbeat ack channel."""
        from jax.experimental import multihost_utils as mh

        return np.asarray(mh.process_allgather(row))


_TRANSPORT: JaxProcessTransport = JaxProcessTransport()


def transport():
    return _TRANSPORT


def install_transport(t) -> object:
    """Swap the control-plane transport (tests install a stub follower
    group; ``None`` restores the real one). Returns the previous
    transport so callers can restore it."""
    global _TRANSPORT
    prev = _TRANSPORT
    _TRANSPORT = t if t is not None else JaxProcessTransport()
    return prev


def broadcast_bytes(payload: bytes | None) -> bytes:
    """Broadcast through the installed transport. The chaos point sits
    BEFORE the first collective: an injected raise/hang here models a peer
    dying (or stalling) pre-broadcast — the one window where failure must
    not desync the follower group."""
    faults.fire("broadcast")  # conlint: contained-by-caller (dispatch_with_retry / pre_swap)
    return transport().broadcast(payload)


class DistributedShardedEngine(ShardedEngine):
    """ShardedEngine over a process-spanning mesh with request fan-out.

    On the coordinator, :meth:`analyze` first replicates the request to
    every follower (bounded + retried, see module docstring), then runs
    the inherited pipeline (whose device step all processes enter
    together); with the follower group declared dead it serves locally
    instead. Followers sit in :meth:`follower_loop` replaying broadcast
    requests until :meth:`shutdown_followers`.
    """

    _LOCAL_STEP_UNBUILT = object()

    def __init__(self, pattern_sets, config=None, mesh=None, clock=None):
        super().__init__(pattern_sets, config, mesh=mesh, clock=clock)
        self.follower_errors = 0  # follower-side malformed-payload count
        self.mesh_health: MeshHealth | None = None
        self.retry_policy = RetryPolicy.from_env()
        self._local_step_cache = self._LOCAL_STEP_UNBUILT
        self._health_thread: threading.Thread | None = None
        self._health_stop: threading.Event | None = None
        if self._is_multiprocess():
            # the golden host fallback is UNSAFE here: a device error on
            # one process would abandon an in-flight collective while the
            # other processes stay blocked inside it, desynchronizing (or
            # deadlocking) the mesh. All processes must fail the same
            # request symmetrically; the server answers with a 500 and the
            # group stays in lockstep for the next broadcast.
            self.fallback_to_golden = False
            self.mesh_health = MeshHealth(transport().process_count())

    def _is_multiprocess(self) -> bool:
        return transport().process_count() > 1

    def _is_coordinator(self) -> bool:
        return transport().process_index() == 0

    # ----------------------------------------------------- bounded dispatch

    def _dispatch_broadcast(
        self, payload: bytes, label: str = "broadcast",
        trace_id: str | None = None,
    ) -> None:
        """One bounded, retried coordinator→follower broadcast. The fault
        sites and the cancellation check both sit BEFORE
        ``enter_collective``, so an abandoned (hung) attempt can never
        emit a stale broadcast after its deadline. When ``trace_id`` is
        given the dispatch stages a ``broadcast`` child span on that
        trace, so mesh fan-out attributes to its originating request."""

        def attempt(ctx):
            faults.fire("follower")  # conlint: contained-by-caller (dispatch_with_retry)
            faults.fire("broadcast")  # conlint: contained-by-caller (dispatch_with_retry)
            ctx.enter_collective()
            transport().broadcast(payload)

        recorder = None
        if trace_id is not None:
            spans = self.obs.spans

            def recorder(duration_s, attrs):
                spans.annotate(trace_id, "broadcast", duration_s, attrs=attrs)

        dispatch_with_retry(
            attempt, self.retry_policy, self.mesh_health, label=label,
            recorder=recorder,
        )

    # ------------------------------------------------------------- analyze

    def analyze(self, data: PodFailureData, request_id: str | None = None):
        if self._is_multiprocess() and self._is_coordinator():
            health = self.mesh_health
            if not health.degraded:
                # the trace id rides the broadcast payload so follower-side
                # work (logs, frames) can attribute to the originating
                # request; followers tolerate the extra key
                payload = json.dumps(
                    {"pod": data.pod, "logs": data.logs,
                     "events": data.events, "rid": request_id}
                ).encode("utf-8")
                try:
                    self._dispatch_broadcast(payload, trace_id=request_id)
                except MeshUnavailable as exc:
                    # the retry budget (or a wedge) already updated health;
                    # make the flip explicit even below the dead_after
                    # threshold — this REQUEST could not be dispatched
                    health.declare_degraded(str(exc))
                    log.error("degrading to local serving: %s", exc)
            if health.degraded:
                return self._analyze_degraded(data)
        return super().analyze(data, request_id=request_id)

    def analyze_pipelined(self, data: PodFailureData, request_id: str | None = None):
        """Multi-process requests cannot pipeline: each request is a
        broadcast + lockstep SPMD dispatch on every process, so two
        concurrent prepare phases would interleave their broadcasts and
        desync the mesh. Serialize the whole request instead (the
        heartbeat probe serializes on the same lock).

        The request scope is entered BEFORE ``state_lock`` — the same
        order :meth:`apply_library` relies on (quiesce, then lock). The
        nested scope inside ``analyze`` is reentrant, so this costs one
        thread-local increment."""
        if self._is_multiprocess():
            with self._request_scope():
                with self.state_lock:
                    return self.analyze(data, request_id=request_id)
        return super().analyze_pipelined(data, request_id=request_id)

    # ----------------------------------------------------- degrade-to-local

    @property
    def _local_step(self) -> ShardedFusedStep | None:
        """Lazy single-process SPMD step over this process's local devices
        — the degraded serving path. None when local devices are unusable
        (then the golden host path serves)."""
        if self._local_step_cache is self._LOCAL_STEP_UNBUILT:
            self._local_step_cache = None
            try:
                import jax

                local = jax.local_devices()
                if local:
                    from log_parser_tpu.parallel.mesh import make_mesh

                    self._local_step_cache = ShardedFusedStep(
                        self.bank,
                        self.config,
                        make_mesh(devices=local),
                        self.matchers,
                        multiprocess=False,
                    )
                    log.info(
                        "degrade-to-local: %d local devices ready", len(local)
                    )
            except Exception:
                log.exception(
                    "degrade-to-local: local step unavailable; degraded "
                    "requests will serve from the golden host path"
                )
        return self._local_step_cache

    def _run_device(self, enc, n_lines: int, om, ov, trace=NO_TRACE):
        # batch rows are padded to a multiple of the GLOBAL mesh size
        # (_corpus_min_rows), which the local device count divides — the
        # local shard_map sees the same shapes, just fewer shards
        if (
            self.mesh_health is not None
            and self.mesh_health.degraded
            and self._is_coordinator()
        ):
            step = self._local_step
            if step is None:
                raise RuntimeError("degraded mode: no usable local devices")
            return self._run_step(step, enc, n_lines, om, ov, trace)
        return super()._run_device(enc, n_lines, om, ov, trace=trace)

    def _analyze_degraded(self, data: PodFailureData):
        """Serve one request without the followers: local SPMD step when
        this process owns devices, golden host path otherwise. The
        response is marked so callers can see it was served degraded."""
        health = self.mesh_health
        health.record_degraded_request()
        if self._local_step is not None:
            result = ShardedEngine.analyze(self, data)
        else:
            result = self._golden_serve(data)
        if result.metadata is not None:
            result.metadata.degraded = DEGRADED_MARKER
        return result

    # ------------------------------------------------------------ heartbeat

    def probe_mesh(self) -> bool:
        """One bounded heartbeat round-trip: broadcast the ``_PING``
        sentinel, gather one ack row ``[process_index, follower_errors]``
        per process, refresh :class:`MeshHealth`, and re-admit a degraded
        mesh on success. Callers in concurrent settings hold
        ``state_lock`` (a probe must never interleave with a request
        broadcast)."""
        if not (self._is_multiprocess() and self._is_coordinator()):
            return True
        health = self.mesh_health
        if health.wedged:
            return False
        t = transport()

        def attempt(ctx):
            faults.fire("heartbeat")  # conlint: contained-by-caller (dispatch_with_retry)
            ctx.enter_collective()
            t.broadcast(_PING)
            row = np.array([t.process_index(), 0], dtype=np.int64)
            return t.allgather(row)

        try:
            acks = dispatch_with_retry(
                attempt, self.retry_policy, health, label="heartbeat"
            )
        except MeshUnavailable as exc:
            health.record_probe(False)
            log.warning("heartbeat failed: %s", exc)
            return False
        for pid, errors in np.asarray(acks).reshape(-1, 2):
            if int(pid) != 0:
                health.record_ack(int(pid), int(errors))
        health.record_probe(True)
        if health.degraded:
            health.readmit()
        return True

    def start_health_loop(self, interval_s: float | None = None):
        """Coordinator-side heartbeat daemon: probes the follower group
        every ``interval_s`` (env ``LOG_PARSER_TPU_HEARTBEAT_S``; 0
        disables). Serializes with requests on ``state_lock``."""
        if not (self._is_multiprocess() and self._is_coordinator()):
            return None
        if self._health_thread is not None:
            return self._health_thread
        if interval_s is None:
            try:
                interval_s = float(os.environ.get(ENV_HEARTBEAT_S, "10"))
            except ValueError:
                interval_s = 10.0
        if interval_s <= 0:
            return None
        stop = threading.Event()

        def loop():
            while not stop.wait(interval_s):
                if self.mesh_health.wedged:
                    continue
                with self.state_lock:
                    if stop.is_set():
                        break
                    self.probe_mesh()

        thread = threading.Thread(target=loop, name="mesh-health", daemon=True)
        self._health_stop = stop
        self._health_thread = thread
        thread.start()
        log.info("mesh health loop up (every %gs)", interval_s)
        return thread

    def stop_health_loop(self) -> None:
        if self._health_stop is not None:
            self._health_stop.set()
        thread = self._health_thread
        self._health_thread = None
        self._health_stop = None
        if thread is not None:
            thread.join(timeout=0.5)  # best-effort; the thread is a daemon

    # ------------------------------------------------------------ followers

    def follower_loop(self) -> None:
        """Run on processes > 0: participate in every broadcast request's
        SPMD dispatches until the coordinator shuts the group down.
        Heartbeat pings are acked inline; malformed payloads are counted
        and skipped — a follower must outlive a coordinator bug."""
        if self._is_coordinator():
            raise RuntimeError("follower_loop must not run on the coordinator")
        t = transport()
        while True:
            payload = broadcast_bytes(None)
            if payload == _SHUTDOWN or payload == b"":
                log.info("follower shutting down")
                return
            if payload == _PING:
                row = np.array(
                    [t.process_index(), self.follower_errors], dtype=np.int64
                )
                t.allgather(row)
                continue
            if payload.startswith(_RELOAD):
                self._apply_reload_payload(payload[len(_RELOAD):])
                continue
            try:
                d = json.loads(payload.decode("utf-8"))
                data = PodFailureData(
                    pod=d.get("pod"),
                    logs=d.get("logs") or "",
                    events=d.get("events"),
                )
            except Exception as exc:
                self.follower_errors += 1
                log.warning(
                    "follower %d: malformed broadcast payload "
                    "(%d bytes, error #%d): %s — skipped",
                    t.process_index(),
                    len(payload),
                    self.follower_errors,
                    exc,
                )
                continue
            try:
                super().analyze(data)
            except Exception:
                # containment: the coordinator saw the same failure on the
                # same deterministic input and answered the client with a
                # 500; the follower stays alive for the next request
                log.exception("follower analyze failed")

    def _apply_reload_payload(self, raw: bytes) -> None:
        """Follower side of a reload-epoch broadcast: rebuild the library
        from the serialized pattern sets and swap in lockstep with the
        coordinator. The coordinator already canary-validated this exact
        library, so the follower applies without its own canary; a
        follower that still fails to build/apply keeps the old banks live
        and counts the error — the next heartbeat ack carries the count
        and the operator sees the epoch skew on /trace/last."""
        from log_parser_tpu.models.pattern import PatternSet
        from log_parser_tpu.runtime.engine import AnalysisEngine

        try:
            doc = json.loads(raw.decode("utf-8"))
            sets = [PatternSet.from_dict(d) for d in doc["sets"]]
            source = AnalysisEngine(sets, self.config)
            self.apply_library(source)
            log.info(
                "follower %d: reload epoch %s applied (%d pattern set(s))",
                transport().process_index(),
                doc.get("epoch"),
                len(sets),
            )
        except Exception:
            self.follower_errors += 1
            log.exception(
                "follower reload failed (error #%d); old banks stay live",
                self.follower_errors,
            )

    def broadcast_reload(self, sets) -> None:
        """Coordinator side: ship the new library to every follower as one
        reload-epoch broadcast. Runs inside apply_library's quiesced
        critical section (see runtime/reload.py), so it can never
        interleave with a request broadcast. A mesh that cannot take the
        broadcast marks itself DEGRADED and the coordinator swaps alone —
        degraded serving is coordinator-local, so responses stay
        consistent until the group is re-seeded."""
        if not (self._is_multiprocess() and self._is_coordinator()):
            return
        health = self.mesh_health
        if health is not None and health.degraded:
            return  # followers are already out of the serving path
        payload = _RELOAD + json.dumps(
            {
                "epoch": self.reload_epoch + 1,
                "sets": [s.to_dict() for s in sets],
            }
        ).encode("utf-8")
        try:
            self._dispatch_broadcast(payload, label="reload")
        except MeshUnavailable as exc:
            if health is not None:
                health.declare_degraded(str(exc))
            log.error(
                "reload broadcast failed — mesh DEGRADED, coordinator "
                "swaps alone: %s", exc,
            )

    def _install_library(self, source) -> None:
        super()._install_library(source)
        # the degrade-to-local step caches a program compiled against the
        # old bank — rebuild lazily on next degraded request
        self._local_step_cache = self._LOCAL_STEP_UNBUILT

    def shutdown_followers(self) -> None:
        if not (self._is_multiprocess() and self._is_coordinator()):
            return
        self.stop_health_loop()
        health = self.mesh_health
        if health is not None and health.wedged:
            # a sentinel into a torn collective would hang this process
            # too; followers exit on their own second-signal path
            log.warning("mesh wedged: skipping the shutdown sentinel")
            return
        try:
            self._dispatch_broadcast(_SHUTDOWN, label="shutdown")
        except MeshUnavailable as exc:
            log.warning(
                "followers unreachable for the shutdown sentinel (%s); "
                "they exit via their own signal handling",
                exc,
            )
