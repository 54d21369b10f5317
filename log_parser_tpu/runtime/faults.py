"""Deterministic fault injection — the chaos half of the robustness story.

The reference service has exactly one failure mode: the JVM falls over
(SURVEY.md §5.2/§5.3). This framework instead carries explicit degradation
machinery (watchdog circuit breaker, golden host fallback, admission
control), and machinery like that is only trustworthy if its failure paths
are *exercised on purpose*. This module is the single switchboard for
doing so: named injection points threaded through the pipeline and the
transports, driven by a config/env DSL with a seeded PRNG and per-point
trigger counts, so every chaos scenario replays identically.

DSL (``LOG_PARSER_TPU_FAULTS``, comma-separated specs)::

    device_raise:0.5,device_hang:2@after=3,ingest_slow:0.05@times=10

Each spec is ``<site>_<action>[:<arg>][@mod=value]*``:

- site: where to inject — ``device``, ``ingest``, ``finalize``, ``http``,
  ``shim``, ``broadcast`` (coordinator-side transport, pre-collective),
  ``follower`` (a follower failing/stalling a dispatch, fired before the
  coordinator commits to the collective), ``heartbeat`` (the liveness
  probe of parallel/resilience.py), ``cache`` (on-disk cache reads —
  contained as a miss, libcache/xlacache), ``batcher`` (micro-batcher
  flush start — ``slow`` delays a flush, ``raise`` fails the whole batch
  into per-request fallback), ``batcher_demux`` (per request during batch
  demux — a dropped demux slot fails ONE request, never its batchmates),
  ``batcher_oversize`` (armed ``raise`` makes the next flush take the
  whole bucket past ``--batch-max`` — an oversized batch),
  ``journal`` (a WAL append failing — contained: the journal goes
  unhealthy, the request is still served), ``journal_torn`` (write half
  a frame then wedge the journal — the recovery-time torn-tail case),
  ``snapshot`` (background snapshot write fails — the WAL is NOT
  truncated, nothing is lost), ``reload_build`` / ``reload_canary``
  (candidate library build / canary validation fails during a hot
  reload — structured 409, the old banks keep serving),
  ``stream`` (per streaming chunk, keyed by the chunk's decoded text —
  a raise kills ONE session with a structured ``error`` frame, never
  the server; runtime/stream.py), ``stream_close`` (the streaming
  finish sequence — a raise rolls back the session's frequency commit
  before the error frame goes out). Any string
  works; sites are just names the code fires, see :func:`fire` call
  sites;
- action: ``raise`` (raise :class:`InjectedFault`; at the ``device`` and
  ``kernel`` sites :class:`InjectedDeviceFault`, which ``is_device_error``
  classifies as a device failure so the golden fallback serves it),
  ``hang`` (block for
  ``arg`` seconds — ``inf`` blocks until :meth:`FaultRegistry.lift`),
  ``slow`` (add ``arg`` seconds of latency);
- arg: probability in (0, 1] for ``raise`` (default 1), seconds for
  ``hang``/``slow``;
- mods: ``after=N`` (skip the first N evaluations at the site),
  ``times=N`` (inject at most N times), ``p=F`` (probability gate for
  ``hang``/``slow``), ``match=SUBSTR`` (content-conditional: the spec is
  eligible only at keyed fire sites — :func:`fire` called with
  ``key=...`` — whose key contains ``SUBSTR``; an unkeyed evaluation
  never matches. This is how a *poison request* is simulated
  deterministically: ``quarantine_raise@match=MARKER`` fails exactly the
  requests carrying MARKER in their logs, wherever they land — alone,
  inside a fused batch, or inside a bisected sub-batch).

The ``quarantine`` site (fired per request at the device-step boundary
with the request's log content as the key) raises
:class:`InjectedPoisonFault` — a *device-classified* fault that, unlike
every other injected fault, also accrues a quarantine strike: it stands
in for an organic poison pill, so the quarantine/bisection machinery
must react to it exactly as to the real thing. Streaming sessions fire
the same site per *chunk* with the chunk's decoded text as the key, so
a ``match=`` spec kills exactly the session that ingests the marker.

Seed: ``LOG_PARSER_TPU_FAULT_SEED`` (default 0). Probabilistic specs draw
from one ``random.Random(seed)`` in evaluation order, so a single-threaded
request sequence reproduces decision-for-decision; count-based specs
(``after``/``times``, p=1) are reproducible even under concurrency.

Zero-cost when idle: :func:`fire` is a module-function no-op until a
registry is installed (env at boot, or :func:`install` from tests).
"""

from __future__ import annotations

import dataclasses
import math
import os
import random
import threading

ENV_SPECS = "LOG_PARSER_TPU_FAULTS"
ENV_SEED = "LOG_PARSER_TPU_FAULT_SEED"

_ACTIONS = ("raise", "hang", "slow")


class InjectedFault(RuntimeError):
    """An injected (not organic) failure. Deliberately NOT classified as a
    device error: an injected ingest/finalize/transport fault must take the
    same propagate-to-500 path a real logic bug would."""

    def __init__(self, point: str, nth: int):
        super().__init__(f"injected fault {point!r} (trigger #{nth})")
        self.point = point
        self.nth = nth


class InjectedDeviceFault(InjectedFault):
    """An injected *device-layer* failure — ``is_device_error`` returns
    True for this class, so the golden fallback (and the breaker
    bookkeeping around it) reacts exactly as it would to a real dead
    backend."""


class InjectedPoisonFault(InjectedDeviceFault):
    """An injected poison *request* (the ``quarantine`` fire site):
    device-classified like :class:`InjectedDeviceFault`, but additionally
    treated as ORGANIC by the quarantine strike rule — injected backend
    chaos (``device_raise``) must never quarantine innocent traffic,
    while an injected poison pill must exercise the whole
    strike/quarantine/bisection ladder end to end."""


class FaultSpecError(ValueError):
    """Malformed ``LOG_PARSER_TPU_FAULTS`` entry."""


@dataclasses.dataclass
class FaultSpec:
    point: str  # full spec name, e.g. "device_hang"
    site: str  # "device"
    action: str  # "hang"
    arg: float  # probability (raise) or seconds (hang/slow)
    p: float = 1.0  # probability gate
    after: int = 0  # skip the first N evaluations
    times: int | None = None  # max injections
    match: str | None = None  # eligible only when the fire key contains this
    # runtime state
    calls: int = 0  # evaluations at this site
    fired: int = 0  # actual injections
    lifted: bool = False
    # hang/slow waiters block on this; lift() releases them
    release: threading.Event = dataclasses.field(
        default_factory=threading.Event
    )


def parse_spec(entry: str) -> FaultSpec:
    """One DSL entry -> FaultSpec. See the module docstring for grammar."""
    entry = entry.strip()
    head, *mods = entry.split("@")
    name, _, argtext = head.partition(":")
    name = name.strip()
    site, sep, action = name.rpartition("_")
    if not sep or action not in _ACTIONS or not site:
        raise FaultSpecError(
            f"bad fault point {name!r} (want <site>_<raise|hang|slow>)"
        )
    arg = 1.0 if action == "raise" else 30.0
    if argtext:
        try:
            arg = float(argtext)
        except ValueError as exc:
            raise FaultSpecError(f"bad arg in {entry!r}") from exc
    spec = FaultSpec(point=name, site=site, action=action, arg=arg)
    if action == "raise":
        if not 0.0 < arg <= 1.0:
            raise FaultSpecError(
                f"raise probability must be in (0, 1]: {entry!r}"
            )
        spec.p = arg
    elif arg < 0:
        raise FaultSpecError(f"negative delay in {entry!r}")
    for mod in mods:
        key, sep, value = mod.partition("=")
        key = key.strip()
        if not sep:
            raise FaultSpecError(f"bad modifier {mod!r} in {entry!r}")
        try:
            if key == "after":
                spec.after = int(value)
            elif key == "times":
                spec.times = int(value)
            elif key == "p":
                spec.p = float(value)
                if not 0.0 < spec.p <= 1.0:
                    raise FaultSpecError(
                        f"p must be in (0, 1]: {entry!r}"
                    )
            elif key == "match":
                if not value:
                    raise FaultSpecError(f"empty match in {entry!r}")
                spec.match = value
            else:
                raise FaultSpecError(f"unknown modifier {key!r} in {entry!r}")
        except ValueError as exc:
            raise FaultSpecError(f"bad modifier {mod!r} in {entry!r}") from exc
    return spec


class FaultRegistry:
    """Parsed fault specs + the seeded PRNG + trigger bookkeeping."""

    def __init__(self, specs: list[FaultSpec], seed: int = 0):
        self.seed = seed
        self.specs = specs
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._by_site: dict[str, list[FaultSpec]] = {}
        for spec in specs:
            self._by_site.setdefault(spec.site, []).append(spec)

    @classmethod
    def parse(cls, text: str, seed: int = 0) -> "FaultRegistry":
        specs = [parse_spec(e) for e in text.split(",") if e.strip()]
        return cls(specs, seed)

    @classmethod
    def from_env(cls, env=None) -> "FaultRegistry | None":
        env = os.environ if env is None else env
        text = env.get(ENV_SPECS, "").strip()
        if not text:
            return None
        return cls.parse(text, int(env.get(ENV_SEED, "0")))

    # ------------------------------------------------------------- firing

    def fire(self, site: str, key: str | None = None) -> None:
        """Evaluate every spec registered at ``site``; the first that
        triggers performs its action (raise / hang / slow). Evaluation
        order is declaration order, draws come from the one seeded RNG.
        ``key`` is the content a ``match=`` spec filters on (the request's
        log blob at per-request sites); a spec with ``match`` set is
        skipped entirely — no counter or RNG advance — when the key does
        not contain its substring."""
        chosen: FaultSpec | None = None
        with self._lock:
            for spec in self._by_site.get(site, ()):
                if spec.match is not None and (
                    key is None or spec.match not in key
                ):
                    continue
                spec.calls += 1
                if spec.lifted or spec.calls <= spec.after:
                    continue
                if spec.times is not None and spec.fired >= spec.times:
                    continue
                if spec.p < 1.0 and self._rng.random() >= spec.p:
                    continue
                if chosen is None:  # later specs still advance counters/RNG
                    spec.fired += 1
                    chosen = spec
        if chosen is None:
            return
        if chosen.action == "raise":
            if site == "quarantine":
                exc_t = InjectedPoisonFault
            elif site in ("device", "kernel"):
                exc_t = InjectedDeviceFault
            else:
                exc_t = InjectedFault
            raise exc_t(chosen.point, chosen.fired)
        # hang/slow: block on the spec's release event so lift() can free
        # waiters; a finite arg is simply the wait timeout
        chosen.release.wait(None if math.isinf(chosen.arg) else chosen.arg)

    # --------------------------------------------------------- management

    def lift(self, point: str | None = None) -> None:
        """Disable matching specs (all when ``point`` is None) and release
        anything currently blocked in their hang/slow waits."""
        with self._lock:
            for spec in self.specs:
                if point is None or spec.point == point:
                    spec.lifted = True
                    spec.release.set()

    def counts(self) -> dict[str, int]:
        """Injections actually performed, per spec point."""
        with self._lock:
            return {s.point: s.fired for s in self.specs}

    def stats(self) -> dict:
        """Reproducibility/observability surface (GET /trace/last)."""
        with self._lock:
            return {
                "seed": self.seed,
                "fired": {s.point: s.fired for s in self.specs},
                "calls": {s.point: s.calls for s in self.specs},
            }


# ------------------------------------------------------- module switchboard

_REGISTRY: FaultRegistry | None = None
_ENV_LOADED = False
_INSTALL_LOCK = threading.Lock()


def install(registry: FaultRegistry | None) -> None:
    """Install (or clear, with None) the active registry — tests and the
    servers' boot paths. Clearing lifts the outgoing registry first so no
    hung waiter outlives it."""
    global _REGISTRY, _ENV_LOADED
    with _INSTALL_LOCK:
        if registry is None and _REGISTRY is not None:
            _REGISTRY.lift()
        _REGISTRY = registry
        _ENV_LOADED = True


def ensure_env() -> None:
    """Parse ``LOG_PARSER_TPU_FAULTS`` once (no-op when unset or when a
    registry was already installed explicitly)."""
    global _REGISTRY, _ENV_LOADED
    with _INSTALL_LOCK:
        if _ENV_LOADED:
            return
        _ENV_LOADED = True
        _REGISTRY = FaultRegistry.from_env()


def active() -> FaultRegistry | None:
    return _REGISTRY


def fire(site: str, key: str | None = None) -> None:
    """Injection point — a no-op unless a registry is installed."""
    reg = _REGISTRY
    if reg is not None:
        reg.fire(site, key)


def stats() -> dict | None:
    reg = _REGISTRY
    return None if reg is None else reg.stats()
