"""Process-wide resource-pressure plane: disk watermarks, memory
watermarks, and retry budgets under one controller.

PRs 4-18 grew the parser into a durable, replicated, routed fleet, and
every one of those layers quietly assumed infinite disk and calm
clients: an ENOSPC on a WAL append, a snapshot rotation, a replica
re-journal, a migration bundle write, or the shutdown OTLP dump used to
surface as an unhandled OSError mid-request (or mid-drain), and the
router/shim retry paths had no budget, so one slow backend could
amplify into a fleet-wide retry storm. This module is the single place
that failure family is decided:

* **Disk** — a watermark monitor over ``--state-dir`` (free-bytes poll
  plus per-write ENOSPC/EIO escalation) drives a three-state ladder:

  - ``ok``   — full fsync'd durability, nothing special.
  - ``soft`` — reclaim: every registered journal snapshots + truncates
    its WAL, the migration and ``_replica/epoch.wal`` journals compact
    past their terminal records, and the miner stops parking pending
    YAML to disk (candidates stay reviewable in memory).
  - ``hard`` — degrade: journals divert appends to a bounded in-memory
    ring and stamp ``durability: degraded`` on ``/q/health``,
    ``/trace/last`` and every response envelope; replica senders pause
    (the receiver refuses feeds with a distinct 409 reason); snapshot
    and OTLP writers skip atomically instead of raising. The serving
    path keeps answering 200s throughout.

  Recovery is hysteretic (free space must clear the watermark by
  :data:`RECOVER_MARGIN`, and a tiny probe write must succeed) and
  re-arms fsync'd journaling from a clean barrier: each journal's
  :meth:`rearm` snapshots the *live* tracker — which holds everything
  the ring records echoed — so a crash after recovery replays exactly
  like one that never saw pressure.

* **Memory** — an RSS watermark (psutil-free, ``/proc/self/statm``)
  composes the levers the earlier PRs built individually — line-cache
  shrink, tenant LRU eviction, span staging trim, miner tap close —
  under one controller: one lever per poll in
  severity order while over the watermark, released in reverse once RSS
  clears the watermark by the same hysteresis margin.

* **Retry budgets** — a token-bucket budget shared per destination
  (every first attempt deposits ``ratio`` tokens, default 10%; every
  retry spends one) wrapped around shim reconnects, router
  forward-follows/next-owner retries, and replica sender backoff, so
  retries shed deterministically (``retry budget exhausted``) instead
  of multiplying load into a storm.

Fault sites (LOG_PARSER_TPU_FAULTS) so drills run on any host without
filling a real disk:

- ``disk_enospc`` — fired with ``key=`` the durability site name at
  every guarded write (:data:`DISK_SITES`) and with
  ``key="watermark:hard"`` / ``key="watermark:soft"`` by the ladder
  poll. ``disk_enospc_raise@match=wal_append`` injects ENOSPC at WAL
  appends only; ``disk_enospc_raise@match=watermark:hard`` forces the
  ladder hard; an unqualified ``disk_enospc_raise`` is a full disk —
  every write fails and the ladder pins hard.
- ``mem_pressure`` — fired by the memory poll; a raise is "RSS is over
  the soft watermark" regardless of the real number.
- ``retry_storm`` — fired inside :meth:`RetryBudget.allow`; a raise is
  an exhausted bucket, so sheds happen deterministically in drills.

Transitions are journaled-then-acted where durable state changes
hands: every reclaim/degrade action rides an existing journal or
atomic-replace discipline (snapshot-before-truncate, tmp+fsync+
``os.replace``), while the ladder state itself is *derived* — a boot
re-polls the same watermarks, so there is nothing to replay.
"""

from __future__ import annotations

import errno
import logging
import os
import threading
import time
from typing import Callable

from log_parser_tpu import _clock as pclock
from log_parser_tpu.runtime import faults

log = logging.getLogger(__name__)

STATES = ("ok", "soft", "hard")
_RANK = {"ok": 0, "soft": 1, "hard": 2}

# free space must clear a watermark by this factor (and a probe write
# must succeed) before the ladder de-escalates — flapping around the
# threshold must not churn snapshot/degrade cycles
RECOVER_MARGIN = 1.25

# records each degraded journal keeps in memory while hard; the ring is
# an *echo* of state the live tracker already holds, so overflow loses
# observability of the oldest diverted records, never state
DEGRADED_RING_RECORDS = 4096

# durability sites guarded by disk_write_guard(); ``@match=<site>``
# selects one. tools/hygiene.py pins each to a docs/OPS.md row.
DISK_SITES = (
    "wal_append",
    "fsync",
    "snapshot_rotate",
    "bundle_write",
    "replica_rejournal",
    "otlp_dump",
)

# watermark-probe keys the ladder poll fires (match targets for drills)
PROBE_HARD = "watermark:hard"
PROBE_SOFT = "watermark:soft"

# chaos vocabulary — tools/hygiene.py pins every key here to a
# docs/OPS.md row AND a live faults.fire call site, exactly like the
# miner/tenancy site tables
FAULT_SITES: dict[str, str] = {
    "disk_enospc": "every guarded durability write (key= the DISK_SITES "
    "name: wal_append/fsync/snapshot_rotate/bundle_write/"
    "replica_rejournal/otlp_dump) and the ladder's watermark probes "
    "(key= watermark:hard then watermark:soft) — a raise is ENOSPC at "
    "that site; unqualified, the disk is simply full",
    "mem_pressure": "the memory-watermark poll — a raise reads as RSS "
    "over the soft watermark, driving the lever ladder without "
    "allocating anything",
    "retry_storm": "RetryBudget.allow (key= the destination) — a raise "
    "is an exhausted bucket, so retries shed deterministically in "
    "drills",
}

_ENOSPC_ERRNOS = frozenset(
    e for e in (
        errno.ENOSPC,
        errno.EIO,
        getattr(errno, "EDQUOT", None),
    ) if e is not None
)


def disk_write_guard(site: str) -> None:
    """Injection point in front of a durability write. Converts an
    injected ``disk_enospc`` raise into an organic ``OSError(ENOSPC)``
    so the *real* containment path under test is exercised — callers
    never special-case injection."""
    try:
        faults.fire("disk_enospc", key=site)
    except faults.InjectedFault as exc:
        raise OSError(errno.ENOSPC, f"injected ENOSPC ({site})") from exc


def rss_bytes() -> int:
    """Resident set size without psutil: ``/proc/self/statm`` field 1
    (pages) times the page size. Returns 0 where /proc is absent (the
    memory ladder then only moves under an injected ``mem_pressure``)."""
    try:
        with open("/proc/self/statm", "rb") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError, AttributeError):
        return 0


class RetryBudget:
    """Token-bucket retry budget shared per destination.

    Every *first* attempt toward a destination deposits ``ratio`` tokens
    (:meth:`note_request`); every retry spends one (:meth:`allow`). The
    bucket starts at ``floor`` (so cold destinations can still retry)
    and caps at ``cap`` (so a quiet hour cannot bank an unbounded
    burst). Sustained retry throughput is therefore at most ``ratio``
    times request throughput — the classic 10% budget — and when the
    bucket runs dry the caller sheds with ``retry budget exhausted``
    instead of piling on. ``ratio <= 0`` disables the budget entirely
    (every retry allowed), which is also the drill's unbounded control.
    """

    def __init__(self, ratio: float = 0.1, *, floor: float = 3.0,
                 cap: float = 50.0):
        self.ratio = float(ratio)
        self.floor = float(floor)
        self.cap = float(cap)
        self._mu = threading.Lock()
        self._tokens: dict[str, float] = {}
        self.requests = 0
        self.allowed = 0
        self.shed = 0

    @property
    def enabled(self) -> bool:
        return self.ratio > 0.0

    def note_request(self, dest: str) -> None:
        """Account one first attempt toward ``dest`` (NOT a retry)."""
        if not self.enabled:
            return
        with self._mu:
            self.requests += 1
            have = self._tokens.get(dest, self.floor)
            self._tokens[dest] = min(self.cap, have + self.ratio)

    def allow(self, dest: str) -> bool:
        """Spend one retry token toward ``dest``; False means shed."""
        if not self.enabled:
            return True
        try:
            faults.fire("retry_storm", key=dest)
        except faults.InjectedFault:
            with self._mu:
                self.shed += 1
            return False
        with self._mu:
            have = self._tokens.get(dest, self.floor)
            if have >= 1.0:
                self._tokens[dest] = have - 1.0
                self.allowed += 1
                return True
            self.shed += 1
            return False

    def stats(self) -> dict:
        with self._mu:
            return {
                "enabled": self.enabled,
                "ratio": self.ratio,
                "requests": self.requests,
                "allowed": self.allowed,
                "shed": self.shed,
                "destinations": len(self._tokens),
            }


class PressureController:
    """One controller per process: the disk ladder, the memory lever
    chain, and the shared retry budget. Everything is inert until
    watermarks are configured (or a fault site forces a state), so the
    default boot is byte-identical to the pre-pressure behaviour."""

    def __init__(
        self,
        state_dir: str | None,
        *,
        disk_soft_mb: float = 0.0,
        disk_hard_mb: float = 0.0,
        mem_soft_mb: float = 0.0,
        retry_ratio: float = 0.1,
        poll_s: float = 1.0,
        clock: Callable[[], float] = pclock.mono,
    ):
        self.state_dir = str(state_dir) if state_dir else None
        self.disk_soft_bytes = max(0, int(float(disk_soft_mb) * 2**20))
        self.disk_hard_bytes = max(0, int(float(disk_hard_mb) * 2**20))
        self.mem_soft_bytes = max(0, int(float(mem_soft_mb) * 2**20))
        self.poll_s = float(poll_s)
        self.clock = clock
        self.retry = RetryBudget(retry_ratio)

        self._mu = threading.RLock()
        self.disk_state = "ok"
        self.mem_state = "ok"
        self.transitions: dict[tuple[str, str], int] = {}
        self.write_errors = 0  # ENOSPC/EIO escalations observed
        self.free_bytes_last = -1
        self.rss_last = 0

        self._journals: list = []  # degrade()/rearm()/snapshot_now()
        self._compactors: list[tuple[str, Callable[[], int]]] = []
        self._miners: list = []
        self._levers: list[tuple[str, Callable, Callable | None]] = []
        self._applied = 0  # memory levers currently applied
        self.lever_counts: dict[str, int] = {}
        self.compacted: dict[str, int] = {}

        self._obs = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # ------------------------------------------------------- registration

    def register_journal(self, journal) -> None:
        """A journal exposing ``snapshot_now()``, ``degrade()`` and
        ``rearm()`` (runtime/journal.py FrequencyJournal). Soft pressure
        snapshots+truncates it; hard degrades it; recovery re-arms it."""
        with self._mu:
            self._journals.append(journal)
            if self.disk_state == "hard":
                journal.degrade()

    def unregister_journal(self, journal) -> None:
        with self._mu:
            try:
                self._journals.remove(journal)
            except ValueError:
                pass

    def register_compactor(self, name: str, fn: Callable[[], int]) -> None:
        """A bounded-growth reclaimer (migration-journal / epoch-WAL
        compaction) run at bootstrap and on every entry into soft. The
        callable returns how many journal files it compacted."""
        with self._mu:
            self._compactors.append((name, fn))

    def register_miner(self, miner) -> None:
        """Miner whose pending-YAML parking pauses under soft+ (it
        consults :func:`miner_park_paused` through the switchboard)."""
        with self._mu:
            self._miners.append(miner)

    def add_lever(self, name: str, apply: Callable[[], None],
                  release: Callable[[], None] | None = None) -> None:
        """Memory lever, registered in severity order. ``apply`` fires
        once as the ladder escalates (one lever per poll); ``release``
        (optional) undoes it when RSS clears the watermark."""
        with self._mu:
            self._levers.append((name, apply, release))

    def bind_obs(self, obs) -> None:
        """Attach the primary Obs bundle: transition spans + the
        ``logparser_pressure_*`` collector."""
        self._obs = obs
        obs.registry.register_collector("pressure", self.metric_samples)

    # ----------------------------------------------------------- ladders

    def bootstrap(self) -> None:
        """Boot-time pass: run compactors once (journals must not grow
        without bound across restarts) and take an initial poll so the
        first request already sees the true state."""
        self._run_compactors()
        self.poll()

    def free_disk_bytes(self) -> int:
        if not self.state_dir:
            return -1
        try:
            st = os.statvfs(self.state_dir)
            return int(st.f_bavail) * int(st.f_frsize)
        except OSError:
            return -1

    def _probe_write(self) -> bool:
        """Can the state dir actually take bytes again? A tiny
        write+fsync+unlink — required before de-escalating out of hard
        so an ENOSPC-escalated state never clears on a statvfs that
        looks fine while writes still fail."""
        if not self.state_dir:
            return True
        path = os.path.join(self.state_dir, ".pressure.probe")
        try:
            with open(path, "wb") as f:
                f.write(b"ok")
                f.flush()
                os.fsync(f.fileno())
            os.unlink(path)
            return True
        except OSError:
            return False

    def poll(self) -> None:
        """One evaluation of both ladders; the background thread calls
        this on the interval, tests call it directly."""
        self._poll_disk()
        self._poll_mem()

    def _poll_disk(self) -> None:
        forced = None
        try:
            faults.fire("disk_enospc", key=PROBE_HARD)
        except faults.InjectedFault:
            forced = "hard"
        if forced is None:
            try:
                faults.fire("disk_enospc", key=PROBE_SOFT)
            except faults.InjectedFault:
                forced = "soft"

        free = self.free_disk_bytes()
        self.free_bytes_last = free
        target = "ok"
        if forced is not None:
            target = forced
        elif free >= 0:
            if self.disk_hard_bytes and free <= self.disk_hard_bytes:
                target = "hard"
            elif self.disk_soft_bytes and free <= self.disk_soft_bytes:
                target = "soft"

        with self._mu:
            current = self.disk_state
            if _RANK[target] > _RANK[current]:
                self._transition_disk(target)
            elif _RANK[target] < _RANK[current]:
                # hysteresis: clear the watermark we are leaving by the
                # margin, and prove the disk takes writes again
                threshold = (
                    self.disk_hard_bytes if current == "hard"
                    else self.disk_soft_bytes
                )
                cleared = (
                    free < 0
                    or threshold == 0
                    or free > threshold * RECOVER_MARGIN
                )
                if cleared and self._probe_write():
                    self._transition_disk(target)

    def _poll_mem(self) -> None:
        over = False
        try:
            faults.fire("mem_pressure")
        except faults.InjectedFault:
            over = True
        rss = rss_bytes()
        self.rss_last = rss
        if not over and self.mem_soft_bytes and rss > self.mem_soft_bytes:
            over = True

        with self._mu:
            if over:
                if self.mem_state != "soft":
                    self._note_transition("memory", "soft")
                    self.mem_state = "soft"
                self._apply_next_lever()
            elif self.mem_state == "soft":
                # hysteresis on release too: stay soft until RSS clears
                # the watermark by the margin (forced-over polls count
                # as not-cleared only while the fault keeps firing)
                if (
                    not self.mem_soft_bytes
                    or rss * RECOVER_MARGIN < self.mem_soft_bytes
                    or rss == 0
                ):
                    self._release_levers()
                    self._note_transition("memory", "ok")
                    self.mem_state = "ok"

    # ------------------------------------------------------- transitions

    def _note_transition(self, resource: str, state: str) -> None:
        key = (resource, state)
        self.transitions[key] = self.transitions.get(key, 0) + 1
        obs = self._obs
        if obs is not None:
            try:
                obs.spans.end_trace(
                    f"pressure:{resource}",
                    duration_s=0.0,
                    tenant="default",
                    name="pressure",
                    attrs={"resource": resource, "state": state},
                    force=True,
                )
            except Exception:  # noqa: BLE001 — observability must not
                # gate a pressure transition
                log.exception("pressure span emit failed")
        log.warning("pressure: %s -> %s", resource, state)

    def _transition_disk(self, target: str) -> None:
        """Caller holds ``_mu``. Escalation and recovery actions both
        ride existing journal/atomic-replace disciplines — the ladder
        state itself is derived, never persisted."""
        current = self.disk_state
        self._note_transition("disk", target)
        self.disk_state = target
        if _RANK[target] > _RANK[current]:
            if target in ("soft", "hard") and current == "ok":
                self._enter_soft()
            if target == "hard":
                self._enter_hard()
        else:
            if current == "hard":
                self._rearm_journals()

    def _live_journals(self) -> list:
        """Prune journals closed since registration (tenant evictions
        close their WAL; nothing unregisters for them) and return the
        live set."""
        with self._mu:
            self._journals = [
                j for j in self._journals
                if getattr(j, "_fp", None) is not None or j.degraded
            ]
            return list(self._journals)

    def _enter_soft(self) -> None:
        """Reclaim: snapshot+truncate every WAL, compact the protocol
        journals. Each action is atomic on its own (tmp+fsync+replace /
        truncate-under-lock), so a crash mid-reclaim is recoverable."""
        for journal in self._live_journals():
            try:
                journal.snapshot_now()
            except Exception:  # noqa: BLE001 — reclaim is best-effort;
                # a failing journal already contained the error
                log.exception("soft-pressure snapshot failed")
        self._run_compactors()

    def _enter_hard(self) -> None:
        for journal in self._live_journals():
            try:
                journal.degrade()
            except Exception:  # noqa: BLE001
                log.exception("journal degrade failed")

    def _rearm_journals(self) -> None:
        """Recovery barrier: every degraded journal snapshots the live
        tracker (which holds everything the ring echoed) and resumes
        fsync'd appends — a crash after this replays bit-identically to
        one that never saw pressure."""
        for journal in self._live_journals():
            try:
                journal.rearm()
            except Exception:  # noqa: BLE001
                log.exception("journal rearm failed")

    def _run_compactors(self) -> None:
        for name, fn in list(self._compactors):
            try:
                n = int(fn() or 0)
            except Exception:  # noqa: BLE001 — compaction must never
                # take the process down; growth resumes, nothing lost
                log.exception("compactor %s failed", name)
                continue
            if n:
                self.compacted[name] = self.compacted.get(name, 0) + n

    def _apply_next_lever(self) -> None:
        if self._applied >= len(self._levers):
            return
        name, apply, _ = self._levers[self._applied]
        self._applied += 1
        try:
            apply()
            self.lever_counts[name] = self.lever_counts.get(name, 0) + 1
            log.warning("memory pressure: lever %r applied", name)
        except Exception:  # noqa: BLE001 — a broken lever must not stop
            # the ladder from trying the next one
            log.exception("memory lever %r failed", name)

    def _release_levers(self) -> None:
        for name, _, release in reversed(self._levers[: self._applied]):
            if release is None:
                continue
            try:
                release()
                log.info("memory pressure cleared: lever %r released", name)
            except Exception:  # noqa: BLE001
                log.exception("memory lever %r release failed", name)
        self._applied = 0

    # ----------------------------------------------------- escalation API

    def note_write_error(self, exc: BaseException, site: str = "") -> None:
        """Per-write escalation: an organic (or injected-then-converted)
        ENOSPC/EIO observed by a durability writer pins the ladder hard
        immediately — watermark polls alone would race the very next
        append."""
        e = getattr(exc, "errno", None)
        if e not in _ENOSPC_ERRNOS:
            return
        with self._mu:
            self.write_errors += 1
            if self.disk_state != "hard":
                log.error(
                    "pressure: write error at %s (%s) — degrading", site, exc
                )
                self._transition_disk("hard")

    # ------------------------------------------------------------ queries

    def durability_degraded(self) -> bool:
        return self.disk_state == "hard"

    def writes_paused(self) -> bool:
        return self.disk_state == "hard"

    def miner_park_paused(self) -> bool:
        return self.disk_state != "ok"

    # ---------------------------------------------------------- lifecycle

    def start(self) -> "PressureController":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="pressure", daemon=True
            )
            self._thread.start()
        return self

    def _loop(self) -> None:
        while not pclock.wait(self._stop, self.poll_s):
            self.poll()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    # -------------------------------------------------------------- stats

    def degraded_writes(self) -> int:
        return sum(
            int(getattr(j, "degraded_records", 0)) for j in list(self._journals)
        )

    def stats(self) -> dict:
        with self._mu:
            return {
                "disk": self.disk_state,
                "memory": self.mem_state,
                "freeBytes": self.free_bytes_last,
                "rssBytes": self.rss_last,
                "diskSoftBytes": self.disk_soft_bytes,
                "diskHardBytes": self.disk_hard_bytes,
                "memSoftBytes": self.mem_soft_bytes,
                "writeErrors": self.write_errors,
                "degradedWrites": self.degraded_writes(),
                "transitions": {
                    f"{r}:{s}": n for (r, s), n in sorted(self.transitions.items())
                },
                "levers": dict(self.lever_counts),
                "compacted": dict(self.compacted),
                "retry": self.retry.stats(),
            }

    def health_check(self) -> dict:
        """One /q/health check row; DEGRADED whenever either ladder has
        left ``ok`` (the server still answers 200s — that is the point)."""
        with self._mu:
            ok = self.disk_state == "ok" and self.mem_state == "ok"
            return {
                "name": "pressure",
                "status": "UP" if ok else "DEGRADED",
                "data": {
                    "disk": self.disk_state,
                    "memory": self.mem_state,
                    "degradedWrites": self.degraded_writes(),
                },
            }

    def metric_samples(self) -> list:
        with self._mu:
            out = [
                ("logparser_pressure_state",
                 {"resource": "disk"}, float(_RANK[self.disk_state])),
                ("logparser_pressure_state",
                 {"resource": "memory"}, float(_RANK[self.mem_state])),
                ("logparser_pressure_degraded_writes_total",
                 {}, float(self.degraded_writes())),
            ]
            for (resource, state), n in sorted(self.transitions.items()):
                out.append((
                    "logparser_pressure_transitions_total",
                    {"resource": resource, "state": state}, float(n),
                ))
            for lever, n in sorted(self.lever_counts.items()):
                out.append((
                    "logparser_pressure_levers_total",
                    {"lever": lever}, float(n),
                ))
            r = self.retry
            out.append(("logparser_pressure_retry_total",
                        {"outcome": "allowed"}, float(r.allowed)))
            out.append(("logparser_pressure_retry_total",
                        {"outcome": "shed"}, float(r.shed)))
            return out


# ------------------------------------------------------- module switchboard
#
# journal/migrate/replicate/miner sit below the serving layer and cannot
# be handed a controller at construction without threading it through a
# dozen signatures — the same reasoning as faults.py's switchboard. The
# default (no controller installed) is inert: every query answers "ok".

_CONTROLLER: PressureController | None = None


def install(controller: PressureController | None) -> None:
    """Install (or clear, with None) the process-wide controller —
    serve boot and tests. Clearing stops the outgoing poll thread."""
    global _CONTROLLER
    old, _CONTROLLER = _CONTROLLER, controller
    if old is not None and old is not controller:
        old.stop()


def current() -> PressureController | None:
    return _CONTROLLER


def durability_degraded() -> bool:
    c = _CONTROLLER
    return c is not None and c.durability_degraded()


def writes_paused() -> bool:
    c = _CONTROLLER
    return c is not None and c.writes_paused()


def miner_park_paused() -> bool:
    c = _CONTROLLER
    return c is not None and c.miner_park_paused()


def note_write_error(exc: BaseException, site: str = "") -> None:
    c = _CONTROLLER
    if c is not None:
        c.note_write_error(exc, site)


def retry_budget() -> RetryBudget | None:
    c = _CONTROLLER
    return None if c is None else c.retry


def stamp(payload: dict) -> dict:
    """Mark a response envelope when durability is degraded. The stamp
    is explicit and structural — clients and drills key on it, so its
    absence is a *promise* that fsync'd journaling is armed."""
    if durability_degraded():
        payload["durability"] = "degraded"
    return payload
